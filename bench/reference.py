"""The plain reference and the comparison that decides ``correct``.

The reference is exact L2 k-nearest-neighbour search over the benchmark's own
float32 corpus (``datagen``), written here without any of the program's
code: for each block of rows, squared distances by a HIGHEST-precision
matmul pick ``CANDIDATES`` rows per query, and those candidates are ranked
again by the direct sum of squared differences, so the k returned are exact
to float32 rounding.  The rotation the program applies is orthogonal, so
distances in the raw space are the distances it is asked for.

Numbers compared, over every answer that came back (``CHECKS``):

  dist_gap       widest gap between a returned distance and the distance
                 recomputed from the raw corpus row of the returned id, as a
                 share of that query's true k-th distance.  An id of -1, an
                 id repeated within one answer or a non-finite distance reads
                 as infinite.
  dist_gap_mean  the same gap, averaged over every returned row.
  recall_deficit 1 - mean recall@k against the reference.
  rank_gap       widest amount by which a returned row lies beyond the true
                 k-th distance, as a share of it.

The control (``Control``) is the reference put in the program's place one
precision step below what the configuration states: int8 rows for a
configuration that serves bfloat16 rows.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CANDIDATES = 64
QUERY_CHUNK = 512
ROW_CHUNK = 131072
CHECKS = ("dist_gap", "dist_gap_mean", "recall_deficit", "rank_gap")


@partial(jax.jit, static_argnames=("cand",))
def _block_candidates(rows, q, *, cand: int):
    d2 = (jnp.sum(q * q, 1)[:, None] + jnp.sum(rows * rows, 1)[None, :]
          - 2.0 * jnp.matmul(q, rows.T, precision=HIGHEST))
    neg, idx = jax.lax.top_k(-d2, cand)
    return -neg, idx


@jax.jit
def _pair_d2(rows, q, local_ids):
    """Direct-form squared distance of q[i] to rows[local_ids[i, j]]; ids
    outside the block give +inf."""
    ok = (local_ids >= 0) & (local_ids < rows.shape[0])
    c = rows[jnp.clip(local_ids, 0, rows.shape[0] - 1)]
    diff = c - q[:, None, :]
    return jnp.where(ok, jnp.sum(diff * diff, axis=-1), jnp.inf)


def _chunks(n, size):
    return [(s, min(s + size, n)) for s in range(0, n, size)]


def pair_distances(blocks, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Exact distances of queries[i] to global rows ids[i, j] (inf for ids
    that name no row)."""
    per = blocks[0].shape[0]
    out = np.full(ids.shape, np.inf, np.float64)
    for s, e in _chunks(len(queries), QUERY_CHUNK):
        for b, rows in enumerate(blocks):
            dev = rows.devices().pop()
            local = jax.device_put(ids[s:e].astype(np.int32) - b * per, dev)
            d2 = np.asarray(_pair_d2(rows, jax.device_put(queries[s:e], dev),
                                     local), np.float64)
            out[s:e] = np.minimum(out[s:e], d2)
    return np.sqrt(out)


def exact_knn(blocks, queries: np.ndarray, k: int):
    """(dists (Q, k), ids (Q, k)) exact, ascending, over all blocks."""
    per = blocks[0].shape[0]
    cand_d, cand_i = [], []
    for b, rows in enumerate(blocks):
        dev = rows.devices().pop()
        for r0, r1 in _chunks(per, ROW_CHUNK):
            part = rows[r0:r1]
            for s, e in _chunks(len(queries), QUERY_CHUNK):
                d, i = _block_candidates(part, jax.device_put(queries[s:e], dev),
                                         cand=min(CANDIDATES, r1 - r0))
                cand_d.append((s, np.asarray(d)))
                cand_i.append((s, np.asarray(i) + b * per + r0))
    nq = len(queries)
    all_d = [[] for _ in range(nq)]
    all_i = [[] for _ in range(nq)]
    for (s, d), (_, i) in zip(cand_d, cand_i):
        for j in range(d.shape[0]):
            all_d[s + j].append(d[j])
            all_i[s + j].append(i[j])
    approx_d = np.stack([np.concatenate(x) for x in all_d])
    approx_i = np.stack([np.concatenate(x) for x in all_i])
    keep = np.argsort(approx_d, axis=1, kind="stable")[:, :CANDIDATES]
    shortlist = np.take_along_axis(approx_i, keep, axis=1)
    exact = pair_distances(blocks, queries, shortlist)
    order = np.argsort(exact, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(exact, order, axis=1),
            np.take_along_axis(shortlist, order, axis=1))


def check_numbers(blocks, queries, served_d, served_i, ref_d, ref_i):
    """The compared numbers for answers ``served_*`` (A, k) to ``queries``
    (A, dim), whose exact answers are ``ref_*``."""
    k = ref_i.shape[1]
    served_i = np.asarray(served_i, np.int64)
    served_d = np.asarray(served_d, np.float64)
    true_d = pair_distances(blocks, queries, served_i)
    kth = np.maximum(ref_d[:, -1], 1e-12)[:, None]
    srt = np.sort(served_i, axis=1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1, keepdims=True)
    bad = (served_i < 0) | ~np.isfinite(served_d) | ~np.isfinite(true_d) | dup
    gap = np.where(bad, np.inf, np.abs(served_d - true_d) / kth)
    beyond = np.where(bad, np.inf, np.maximum(true_d - ref_d[:, -1:], 0.0) / kth)
    hits = [len(set(a.tolist()) & set(b.tolist())) for a, b in zip(served_i, ref_i)]
    return {"dist_gap": float(gap.max()),
            "dist_gap_mean": float(gap.mean()),
            "recall_deficit": 1.0 - float(np.mean(hits)) / k,
            "rank_gap": float(beyond.max())}


@jax.jit
def _int8(x):
    """Symmetric int8 per 128-dim block (the serving layout), dequantized."""
    blk = x.reshape(x.shape[0], x.shape[1] // 128, 128)
    s = jnp.max(jnp.abs(blk), axis=(0, 2), keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return (jnp.round(blk / s) * s).reshape(x.shape)


# One precision step below the rows a configuration serves.
LOWER = {"bfloat16": _int8}


@jax.jit
def _pca_basis(sample):
    c = sample - jnp.mean(sample, axis=0)
    cov = jnp.matmul(c.T, c, precision=HIGHEST) / sample.shape[0]
    return jnp.linalg.eigh(cov)[1][:, ::-1]


@partial(jax.jit, static_argnames=("dtype",))
def _lower(x, basis, *, dtype):
    return LOWER[dtype](jnp.matmul(x, basis, precision=HIGHEST))


class Control:
    """The reference in the program's place, one precision step below the
    rows the configuration serves, in the serving layout: rows turned into
    the reference's own PCA basis (fitted on 50,000 rows of the first
    block, as the program fits its own) and rounded there, per 128-dim
    block for int8.  Queries are turned and rounded the same way."""

    def __init__(self, blocks, rows_dtype: str, sample: int = 50_000):
        self.dtype = rows_dtype
        basis = _pca_basis(blocks[0][:sample])
        self.bases = [jax.device_put(basis, b.devices().pop()) for b in blocks]
        self.rows = [_lower(b, m, dtype=rows_dtype)
                     for b, m in zip(blocks, self.bases)]

    def answers(self, queries: np.ndarray, k: int):
        basis = self.bases[0]
        q = jax.device_put(np.asarray(queries, np.float32), basis.devices().pop())
        return exact_knn(self.rows, np.asarray(_lower(q, basis, dtype=self.dtype)), k)
