"""Wave-synchronous K-NN refinement on top of the batched DCO engine.

Replaces the paper's sequential max-heap (`Q` in §1) with a TPU-friendly
running top-K: the corpus is consumed in fixed-size waves; within a wave the
threshold r (current K-th best) is frozen, between waves the survivors merge
into the running top-K via ``jax.lax.top_k``.  Freezing r within a wave is
conservative — it can only admit extra candidates — so recall is >= the
paper's per-candidate semantics (DESIGN.md §3.1).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.calibration import EpsilonTable
from repro.core.dco import dco_screen_batch

__all__ = ["KnnResult", "knn_search_waves", "exact_knn", "merge_topk", "seed_threshold"]

_INF = jnp.float32(jnp.inf)


class KnnResult(NamedTuple):
    dists: jax.Array  # (Q, K) exact distances, ascending
    ids: jax.Array  # (Q, K) corpus row ids (int32), -1 for unfilled
    avg_dims: jax.Array  # scalar: mean dimensions scanned per candidate


def merge_topk(
    top_sq: jax.Array,  # (Q, K)
    top_ids: jax.Array,  # (Q, K)
    new_sq: jax.Array,  # (Q, W) (inf where invalid)
    new_ids: jax.Array,  # (Q, W)
) -> tuple[jax.Array, jax.Array]:
    """Merge wave survivors into the running top-K (ascending distances)."""
    k = top_sq.shape[1]
    all_sq = jnp.concatenate([top_sq, new_sq], axis=1)
    all_ids = jnp.concatenate([top_ids, new_ids], axis=1)
    neg, idx = jax.lax.top_k(-all_sq, k)
    return -neg, jnp.take_along_axis(all_ids, idx, axis=1)


@partial(jax.jit, static_argnames=("k",))
def exact_knn(queries: jax.Array, corpus: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Brute-force ground truth: (Q, K) dists and ids.

    The dot runs at HIGHEST precision: TPU's default f32 matmul is a single
    bf16 pass, too coarse for a reference that recall is measured against.
    """
    q = queries.astype(jnp.float32)
    c = corpus.astype(jnp.float32)
    sq = (
        jnp.sum(q * q, axis=1)[:, None]
        + jnp.sum(c * c, axis=1)[None, :]
        - 2.0 * jnp.matmul(q, c.T, precision=jax.lax.Precision.HIGHEST)
    )
    neg, idx = jax.lax.top_k(-sq, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


def seed_threshold(
    q_rot: jax.Array, corpus_rot: jax.Array, table: EpsilonTable, k: int
) -> jax.Array:
    """Two-phase search, phase 1: cheap global r estimate from the first
    checkpoint's dims only.  Returns (Q,) squared-threshold seeds.

    This is a beyond-paper optimization for the distributed setting: with a
    tight initial r every shard prunes aggressively from the first wave,
    instead of spending full-D distances until the heap warms up.
    The seed is inflated by 1/(1-eps_lo_1)^2 (the calibration's lower-tail
    quantile): an estimate may undershoot its true distance by eps_lo with
    probability P_s, so the inflated seed still covers the true k-th NN
    (keeps the Lemma-5 failure accounting).
    """
    d0 = table.dims[0]
    m = (jnp.arange(q_rot.shape[1]) < d0).astype(q_rot.dtype)
    qm = q_rot * m[None, :]
    cm = corpus_rot * m[None, :]
    sq = (
        jnp.sum(qm * qm, axis=1)[:, None]
        + jnp.sum(cm * cm, axis=1)[None, :]
        - 2.0 * jnp.matmul(qm, cm.T, precision=jax.lax.Precision.HIGHEST)
    )
    est_sq = jnp.maximum(sq, 0.0) * table.scale[0]
    _, idx = jax.lax.top_k(-est_sq, k)  # (Q, K) candidate ids by estimate
    # Verify the K candidates EXACTLY (K full-D distances per query — cheap):
    # the K-th exact distance of any K candidates upper-bounds the global
    # K-th, deterministically.  Quantile inflation of the estimated K-th is
    # NOT safe: it is a min-order statistic, selection-biased low.
    cand = jnp.take(corpus_rot, idx.reshape(-1), axis=0).reshape(
        idx.shape[0], idx.shape[1], -1)  # (Q, K, D)
    diff = cand - q_rot[:, None, :]
    exact_sq = jnp.sum(diff.astype(jnp.float32) ** 2, axis=-1)  # (Q, K)
    kth = jnp.max(exact_sq, axis=1)
    # Widen by the overshoot band so a true neighbor whose own first
    # estimate overshoots is still admitted at the first checkpoint.
    return kth * (1.0 + table.eps[0]) ** 2


@partial(jax.jit, static_argnames=("k", "wave", "two_phase"))
def knn_search_waves(
    queries_rot: jax.Array,  # (Q, D) rotated queries
    corpus_rot: jax.Array,  # (N, D) rotated corpus
    table: EpsilonTable,
    *,
    k: int,
    wave: int = 4096,
    two_phase: bool = False,
) -> KnnResult:
    """Linear-scan K-NN with DCO screening (the paper's Fig. 3 workload)."""
    qn, dim = queries_rot.shape
    n = corpus_rot.shape[0]
    if n % wave != 0:
        # Pad with a large finite sentinel (inf would poison the masked
        # matmuls in dco_screen_batch with inf*0 = NaN).
        pad = wave - n % wave
        corpus_rot = jnp.concatenate(
            [corpus_rot, jnp.full((pad, dim), 1e18, corpus_rot.dtype)], axis=0
        )
        n = corpus_rot.shape[0]
    num_waves = n // wave
    waves = corpus_rot.reshape(num_waves, wave, dim)

    if two_phase:
        r0 = seed_threshold(queries_rot, corpus_rot, table, k)
    else:
        r0 = jnp.full((qn,), _INF)

    init = (
        jnp.full((qn, k), _INF),  # top_sq
        jnp.full((qn, k), -1, jnp.int32),  # top_ids
        r0,  # r_sq
        jnp.zeros((), jnp.float32),  # dims accumulator
    )

    def step(carry, xs):
        top_sq, top_ids, r_sq, dims_acc = carry
        wave_rows, wave_base = xs
        res = dco_screen_batch(queries_rot, wave_rows, table, r_sq)
        ids = wave_base + jnp.arange(wave, dtype=jnp.int32)[None, :]
        new_sq = jnp.where(res.passed, res.est_sq, _INF)
        new_ids = jnp.broadcast_to(ids, res.est_sq.shape)
        top_sq, top_ids = merge_topk(top_sq, top_ids, new_sq, new_ids)
        r_sq = jnp.minimum(r_sq, top_sq[:, -1])
        dims_acc = dims_acc + jnp.sum(res.dims_used.astype(jnp.float32))
        return (top_sq, top_ids, r_sq, dims_acc), None

    bases = (jnp.arange(num_waves, dtype=jnp.int32) * wave)
    (top_sq, top_ids, _, dims_acc), _ = jax.lax.scan(step, init, (waves, bases))
    avg_dims = dims_acc / (qn * n)
    return KnnResult(
        dists=jnp.sqrt(jnp.maximum(top_sq, 0.0)), ids=top_ids, avg_dims=avg_dims
    )
