"""IVF index with pluggable DCO (FDScanning / ADSampling / DADE).

Build: k-means coarse quantizer in the *rotated* space (rotation is
orthogonal so cluster geometry is unchanged — Lemma 1), corpus permuted
cluster-contiguous.  Two search layouts are maintained:

  * **Padded-gather** (``buckets``/``bucket_ids``): clusters padded to a
    common capacity; ``search_ivf`` gathers a ``(Q, cap, D)`` candidate
    tensor per probe and screens it with the vmapped jnp engines.  This is
    the portable fallback (CPU / interpret) and the semantic baseline.
  * **CSR flat** (``starts``/``flat_rot``/``flat_codes``/``flat_ids``,
    built with ``quant="int8"``): the corpus stays flat and
    cluster-contiguous, clusters located by ``starts`` offsets.
    ``search_ivf_fused`` feeds this layout to the fused wave-scan
    megakernel (``repro.kernels.ivf_scan``), which streams bucket tiles
    straight from HBM — no per-probe gather copies — runs the int8×int8
    MXU prefilter + fp32 DADE re-screen, and keeps the top-K/threshold on
    device.  Codes here use per-*block* scales (the int8×int8 MXU needs a
    scalar dequantize per dim-block); the per-dim ``qbuckets`` mirror keeps
    serving the two-stage jnp screen and the threshold seeding.

Search (paper §3.4): pick the n_probe nearest centroids, scan their
buckets as DCO waves, maintain the running top-K whose K-th distance is
the DCO threshold r.  ``seed_r`` (beyond-paper, ROADMAP follow-up) warms r
before wave 0 from exact distances to an int8-prescreened sample of the
nearest bucket, so the first wave already prunes.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dco import dco_screen_batch
from repro.core.estimators import SEED_SLACK, Estimator, build_estimator
from repro.obs.trace import current_tracer
from repro.core.topk import merge_topk
from repro.index.kmeans import kmeans
from repro.kernels.ops import fused_fetch_totals, ivf_scan_kernel, kernel_spec
from repro.quant.accounting import (
    ID_BYTES,
    fetched_tile_bytes,
    stage2_fetch_report,
)
from repro.quant.scalar import (
    QuantizedCorpus,
    fit_block_scales,
    fit_scales,
    quantize,
    quantize_block,
    wants_quant,
)
from repro.quant.screen import two_stage_screen

__all__ = ["IVFIndex", "build_ivf", "search_ivf", "search_ivf_fused",
           "FusedScanStats"]

_SENTINEL = 1e18


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IVFIndex:
    estimator: Estimator
    centroids: jax.Array  # (Nc, D) rotated space
    buckets: jax.Array  # (Nc, cap, D) rotated, padded with _SENTINEL
    bucket_ids: jax.Array  # (Nc, cap) original row ids, -1 padding
    bucket_sizes: jax.Array  # (Nc,)
    # Optional int8 mirror of ``buckets`` (repro.quant): stage-1 of the
    # two-stage screen streams these 1-byte codes; fp32 rows are touched
    # only by surviving candidates.  None when built without quantization.
    qbuckets: jax.Array | None = None  # (Nc, cap, D) int8, 0-padded
    qscales: jax.Array | None = None  # (D,)
    # CSR flat layout for the fused wave-scan megakernel (quant builds).
    # Rows are cluster-contiguous; ``starts[c]`` is cluster c's first row;
    # the tail is sentinel-padded so any probe window stays in bounds.
    starts: jax.Array | None = None  # (Nc + 1,) int32
    flat_rot: jax.Array | None = None  # (N_pad, D_pad) f32
    flat_codes: jax.Array | None = None  # (N_pad, D_pad) int8 per-block
    flat_ids: jax.Array | None = None  # (N_pad,) int32, -1 tail
    bscales: jax.Array | None = None  # (D_pad // scan_block_d,) f32
    # Static layout metadata (hashable aux data, not arrays).
    max_bucket: int = 0
    scan_block_d: int = 0

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def capacity(self) -> int:
        return self.buckets.shape[1]

    @property
    def has_quant(self) -> bool:
        return self.qbuckets is not None

    @property
    def has_fused(self) -> bool:
        return self.flat_codes is not None

    def tree_flatten(self):
        return (
            (self.estimator, self.centroids, self.buckets, self.bucket_ids,
             self.bucket_sizes, self.qbuckets, self.qscales, self.starts,
             self.flat_rot, self.flat_codes, self.flat_ids, self.bscales),
            (self.max_bucket, self.scan_block_d),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        max_bucket, scan_block_d = aux
        return cls(*children, max_bucket=max_bucket, scan_block_d=scan_block_d)


def build_ivf(
    data,
    *,
    method: str = "dade",
    n_clusters: int = 256,
    kmeans_iters: int = 15,
    key: jax.Array | None = None,
    estimator: Estimator | None = None,
    quant: str | None = None,
    scan_block_d: int | None = None,
    **est_kwargs,
) -> IVFIndex:
    """Build an IVF index over (N, D) data. Host-side (one-time, offline).

    ``quant="int8"`` (or an estimator carrying a QuantConfig) additionally
    stores int8 codes per bucket for the two-stage screen AND the CSR flat
    layout + per-block codes for the fused wave-scan kernel.
    ``scan_block_d`` is the fused kernel's dimension-block width (default:
    the estimator's Δd, so the kernel checkpoints coincide with the
    calibrated table; production TPU runs want 128).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    k_est, k_km = jax.random.split(key)
    data = jnp.asarray(data, jnp.float32)
    if estimator is None:
        estimator = build_estimator(method, data, k_est, quant=quant, **est_kwargs)
    rot = np.asarray(estimator.rotate(data))

    cents, assignment = kmeans(k_km, jnp.asarray(rot), n_clusters, kmeans_iters)
    assignment = np.asarray(assignment)

    n = rot.shape[0]
    # Ids/offsets are int32 end-to-end: allocating int64 then downcasting
    # hid a potential overflow.  2^31 rows is far beyond a single host's
    # build anyway (the distributed service shards first).
    if n >= np.iinfo(np.int32).max:
        raise ValueError(f"corpus of {n} rows overflows int32 bucket ids")
    order = np.argsort(assignment, kind="stable").astype(np.int32)
    sizes = np.bincount(assignment, minlength=n_clusters).astype(np.int32)
    cap = int(max(1, sizes.max()))
    # Round capacity up so gathered candidate matrices are lane-aligned.
    cap = ((cap + 127) // 128) * 128

    dim = rot.shape[1]
    buckets = np.full((n_clusters, cap, dim), _SENTINEL, np.float32)
    bucket_ids = np.full((n_clusters, cap), -1, np.int32)
    starts = np.zeros(n_clusters + 1, np.int32)
    np.cumsum(sizes, out=starts[1:])
    assert int(starts[-1]) == n  # int32 cumsum cannot have wrapped
    for c in range(n_clusters):
        rows = order[starts[c] : starts[c + 1]]
        buckets[c, : len(rows)] = rot[rows]
        bucket_ids[c, : len(rows)] = rows

    qbuckets = qscales = None
    flat_rot = flat_codes = flat_ids = bscales = None
    max_bucket = int(sizes.max())
    block_d = 0
    if wants_quant(quant, estimator.quant):
        qscales = np.asarray(fit_scales(jnp.asarray(rot)))
        # Pad slots get code 0 (dequantizes to the origin): stage 1 may keep
        # them, but the fp32 stage sees the _SENTINEL row and the id mask
        # drops them regardless — soundness never depends on pad rows.
        qbuckets = np.zeros((n_clusters, cap, dim), np.int8)
        codes = np.asarray(quantize(jnp.asarray(rot), jnp.asarray(qscales)))
        for c in range(n_clusters):
            rows = order[starts[c] : starts[c + 1]]
            qbuckets[c, : len(rows)] = codes[rows]

        # CSR flat layout for the fused megakernel: cluster-contiguous rows
        # with every cluster's start ALIGNED to the 128-row tile grid
        # (sentinel gap rows between clusters).  Aligned starts mean a probe
        # window of ceil(size/block_c) tiles covers exactly its bucket — no
        # round-down spill into neighbours, so bytes scanned track bucket
        # sizes, not tile geometry (layout decision recorded in ROADMAP).
        # Costs <= Nc·127 extra sentinel rows; dims are zero-padded to the
        # block grid and the tail sentinel-padded so the largest window
        # stays in bounds.
        if scan_block_d is None:
            block_d = int(np.asarray(estimator.table.dims)[0])
        else:
            block_d = int(scan_block_d)
        # Building the fused layout for an estimator the kernel can't
        # express (fixed-dim baselines) is always a mistake — refuse here,
        # by name, not waves deep into the first search.
        kernel_spec(estimator, dim, block_d)
        align = 128
        d_pad = (dim + block_d - 1) // block_d * block_d
        astarts = np.zeros(n_clusters + 1, np.int64)
        np.cumsum((sizes + align - 1) // align * align, out=astarts[1:])
        n_flat = int(astarts[-1])
        n_pad = (n_flat + max_bucket + 2 * align + align - 1) // align * align
        if n_pad >= np.iinfo(np.int32).max:
            raise ValueError("aligned flat layout overflows int32 offsets")
        rot_pad = np.zeros((n, d_pad), np.float32)
        rot_pad[:, :dim] = rot
        bscales = np.asarray(fit_block_scales(jnp.asarray(rot_pad), block_d))
        codes_blk = np.asarray(
            quantize_block(jnp.asarray(rot_pad), jnp.asarray(bscales), block_d))
        flat_rot = np.full((n_pad, d_pad), _SENTINEL, np.float32)
        flat_codes = np.zeros((n_pad, d_pad), np.int8)
        flat_ids = np.full((n_pad,), -1, np.int32)
        for c in range(n_clusters):
            rows = order[starts[c]: starts[c + 1]]
            a = int(astarts[c])
            flat_rot[a: a + len(rows)] = rot_pad[rows]
            flat_codes[a: a + len(rows)] = codes_blk[rows]
            flat_ids[a: a + len(rows)] = rows
        starts = astarts.astype(np.int32)  # fused path sees aligned offsets

    return IVFIndex(
        estimator=estimator,
        centroids=cents,
        buckets=jnp.asarray(buckets),
        bucket_ids=jnp.asarray(bucket_ids, jnp.int32),
        bucket_sizes=jnp.asarray(sizes, jnp.int32),
        qbuckets=None if qbuckets is None else jnp.asarray(qbuckets),
        qscales=None if qscales is None else jnp.asarray(qscales, jnp.float32),
        starts=None if flat_rot is None else jnp.asarray(starts, jnp.int32),
        flat_rot=None if flat_rot is None else jnp.asarray(flat_rot),
        flat_codes=None if flat_codes is None else jnp.asarray(flat_codes),
        flat_ids=None if flat_ids is None else jnp.asarray(flat_ids, jnp.int32),
        bscales=None if bscales is None else jnp.asarray(bscales, jnp.float32),
        max_bucket=max_bucket,
        scan_block_d=block_d,
    )


def _quant_seed_rsq(index: IVFIndex, q_rot: jax.Array, seed_bucket: jax.Array,
                    k: int) -> jax.Array:
    """Quantized threshold seeding (ROADMAP follow-up).

    Prescreens ``seed_bucket``'s rows with the 1-byte int8 codes, verifies
    the k apparent-nearest EXACTLY (k full-D fp32 rows per query — cheap),
    and returns the k-th exact squared distance widened by the
    first-checkpoint overshoot band.  The k-th exact distance of any k real
    candidates deterministically upper-bounds the final k-th, so the seed
    is a sound (conservative) initial r² — wave 0 prunes instead of
    scanning at r = inf.
    """
    table = index.estimator.table
    codes = index.qbuckets[seed_bucket]  # (Q, cap, D) int8 — 1 B/dim stream
    ids = index.bucket_ids[seed_bucket]  # (Q, cap)
    deq = codes.astype(jnp.float32) * index.qscales[None, None, :]
    approx_sq = jnp.sum((deq - q_rot[:, None, :]) ** 2, axis=-1)  # (Q, cap)
    approx_sq = jnp.where(ids >= 0, approx_sq, jnp.inf)
    _, sel = jax.lax.top_k(-approx_sq, k)  # (Q, k) best by int8 estimate
    rows = index.buckets[seed_bucket[:, None], sel]  # (Q, k, D) fp32 gather
    exact_sq = jnp.sum((rows - q_rot[:, None, :]) ** 2, axis=-1)  # (Q, k)
    kth = jnp.max(exact_sq, axis=1)
    # Clamp the all-pad degenerate case (bucket smaller than k) back to inf.
    kth = jnp.where(kth >= _SENTINEL, jnp.inf, kth)
    # SEED_SLACK keeps zero-widening methods (fdscanning: eps[0] = 0) sound
    # when the k-th neighbour is itself a verified seed row.
    return kth * (1.0 + table.eps[0]) ** 2 * (1.0 + SEED_SLACK)


@partial(jax.jit, static_argnames=("k", "n_probe", "use_quant", "seed_r"))
def search_ivf(
    index: IVFIndex,
    queries: jax.Array,
    *,
    k: int = 10,
    n_probe: int = 8,
    use_quant: bool = False,
    seed_r: bool = False,
):
    """Batched IVF search. Returns (dists (Q,K), ids (Q,K), avg_dims scalar).

    Each probed bucket is one DCO wave: the threshold r refreshes between
    buckets (nearest bucket first, so r tightens fast — same ordering as
    Faiss/the paper's IVF*).

    ``use_quant`` routes each wave through the two-stage screen (int8
    lower-bound prefilter + fp32 re-screen of survivors).  Results are
    identical to the fp32 path (no false prunes); ``avg_dims`` then counts
    only fp32 dims — the bytes the prefilter saved are visible as the drop.

    ``seed_r`` (needs a quant build) warms the initial threshold from exact
    distances to an int8-prescreened sample of each query's nearest bucket,
    so wave 0 prunes instead of running at r = inf.
    """
    q = queries.astype(jnp.float32)
    q_rot = index.estimator.rotate(q)
    qn = q_rot.shape[0]
    table = index.estimator.table

    cd = (
        jnp.sum(q_rot * q_rot, axis=1)[:, None]
        + jnp.sum(index.centroids * index.centroids, axis=1)[None, :]
        - 2.0 * q_rot @ index.centroids.T
    )
    _, probe = jax.lax.top_k(-cd, n_probe)  # (Q, P) nearest buckets first

    top_sq = jnp.full((qn, k), jnp.inf)
    top_ids = jnp.full((qn, k), -1, jnp.int32)
    if seed_r:
        if not index.has_quant:
            raise ValueError("search_ivf(seed_r=True) needs quant='int8'")
        r_sq = _quant_seed_rsq(index, q_rot, probe[:, 0], k)
    else:
        r_sq = jnp.full((qn,), jnp.inf)
    dims_acc = jnp.zeros((), jnp.float32)
    rows_acc = jnp.zeros((), jnp.float32)

    if use_quant and not index.has_quant:
        raise ValueError("search_ivf(use_quant=True) needs an index built with quant='int8'")

    def body(p, carry):
        top_sq, top_ids, r_sq, dims_acc, rows_acc = carry
        bucket = probe[:, p]  # (Q,)
        cands = index.buckets[bucket]  # (Q, cap, D)
        cand_ids = index.bucket_ids[bucket]  # (Q, cap)
        valid = cand_ids >= 0

        # Per-query candidate sets: vmap the single-query screen.
        if use_quant:
            qcands = index.qbuckets[bucket]  # (Q, cap, D) int8
            res = jax.vmap(
                lambda qv, cv, qcv, rv: two_stage_screen(
                    qv[None], cv, QuantizedCorpus(qcv, index.qscales), table, rv[None]
                )
            )(q_rot, cands, qcands, r_sq)
        else:
            res = jax.vmap(
                lambda qv, cv, rv: dco_screen_batch(qv[None], cv, table, rv[None])
            )(q_rot, cands, r_sq)
        est_sq = res.est_sq[:, 0, :]  # (Q, cap)
        passed = res.passed[:, 0, :] & valid
        new_sq = jnp.where(passed, est_sq, jnp.inf)
        top_sq, top_ids = merge_topk(top_sq, top_ids, new_sq, cand_ids)
        r_sq = jnp.minimum(r_sq, top_sq[:, -1])
        dims_acc = dims_acc + jnp.sum(
            jnp.where(valid, res.dims_used[:, 0, :], 0).astype(jnp.float32)
        )
        rows_acc = rows_acc + jnp.sum(valid.astype(jnp.float32))
        return top_sq, top_ids, r_sq, dims_acc, rows_acc

    top_sq, top_ids, _, dims_acc, rows_acc = jax.lax.fori_loop(
        0, n_probe, body, (top_sq, top_ids, r_sq, dims_acc, rows_acc)
    )
    avg_dims = dims_acc / jnp.maximum(rows_acc, 1.0)
    return jnp.sqrt(jnp.maximum(top_sq, 0.0)), top_ids, avg_dims


class FusedScanStats(NamedTuple):
    """Per-batch accounting from the fused wave scan (host-side floats).

    ``bytes_per_query`` is the semantic dims-consumed quantity tracked
    since PR 1 (comparable across the BENCH_dco.json trajectory); the
    ``fetched_*``/``s2_*`` fields are DMA-granular — what HBM actually
    shipped under the demand-paged kernel, where a candidate tile whose
    stage-1 survivor count is zero never pays its fp32 block."""

    avg_fp_dims: float  # fp32 dims consumed per scanned row
    avg_int8_dims: float  # int8 dims consumed per scanned row
    rows_per_query: float  # candidate rows screened per query
    bytes_per_query: float  # 1 B/int8 dim + 4 B/fp32 dim, corpus bytes only
    passed_per_query: float  # rows surviving the full screen per query
    s1_tiles_fetched: float = 0.0  # int8 candidate tiles DMA'd for stage 1
    s2_slabs_total: float = 0.0  # fp32 slabs a non-paged pipeline ships
    s2_slabs_fetched: float = 0.0  # fp32 slabs actually DMA'd on demand
    s2_skip_rate: float = 0.0  # 1 - fetched/total (fetch elision)
    fetched_bytes_per_query: float = 0.0  # DMA-granular HBM bytes / query


def _route_tiles(index: IVFIndex, q_rot: jax.Array, *, n_probe: int,
                 block_q: int):
    """Tile-level probe routing for the fused scan.

    Groups queries into tiles of ``block_q`` by nearest centroid and ranks
    each tile's buckets by rank-weighted votes from its queries' own
    top-``n_probe`` lists, tie-broken by the tile-min centroid distance.
    Shared by ``search_ivf_fused`` and the continuous-batching engine (one
    query per tile there), so the probe plan a solo tile gets is THE plan
    the batch oracle would compute for that query alone — the routing half
    of the interleaving-invariance argument is structural.

    Returns ``(order, inv, q_sorted, tile_buckets, window_starts,
    window_rows)``.
    """
    qn = q_rot.shape[0]
    cd = (
        jnp.sum(q_rot * q_rot, axis=1)[:, None]
        + jnp.sum(index.centroids * index.centroids, axis=1)[None, :]
        - 2.0 * q_rot @ index.centroids.T
    )
    # Group queries into tiles of block_q by nearest centroid.
    nearest = jnp.argmin(cd, axis=1)
    order = jnp.argsort(nearest)
    inv = jnp.argsort(order)
    q_sorted = q_rot[order]
    cd_sorted = cd[order]

    q_tiles = (qn + block_q - 1) // block_q
    pad = q_tiles * block_q - qn
    nc = cd.shape[1]
    cd_t = jnp.concatenate(
        [cd_sorted, jnp.full((pad, nc), jnp.inf)], axis=0
    ).reshape(q_tiles, block_q, nc)
    tile_cd = jnp.min(cd_t, axis=1)  # (QT, Nc)
    # Rank a tile's buckets by rank-weighted votes from its queries'
    # OWN top-n_probe lists (weight 1/(rank+1): a query's primary
    # bucket outweighs several mid-rank mentions), tie-broken by the
    # tile-min centroid distance.  Pure min-distance ranking starves
    # queries whose buckets are individually close but never
    # tile-closest; unweighted voting drops primary buckets for
    # popular mid-rank ones — both cost measurable recall on
    # clustered corpora.
    _, q_probe = jax.lax.top_k(-cd_sorted, n_probe)  # (Q, P) per query
    rank_w = 1.0 / (jnp.arange(n_probe, dtype=jnp.float32) + 1.0)
    # Rank-0 gets an overwhelming weight: a tile holds at most block_q
    # distinct top-1 buckets, so with n_probe >= block_q EVERY query's
    # primary bucket — where most of its neighbours live — is
    # guaranteed a slot, whatever the rest of the tile votes.
    rank_w = rank_w.at[0].set(float(n_probe * block_q))
    # Scatter-add, not one_hot: the dense (Q, P, Nc) intermediate
    # would be ~100 MB per call at roadmap scale (Nc ~ thousands).
    votes_q = jnp.zeros((qn, nc), jnp.float32).at[
        jnp.arange(qn)[:, None], q_probe].add(rank_w[None, :])  # (Q, Nc)
    votes = jnp.concatenate(
        [votes_q, jnp.zeros((pad, nc))], axis=0
    ).reshape(q_tiles, block_q, nc).sum(axis=1)  # (QT, Nc)
    finite_cd = jnp.where(jnp.isfinite(tile_cd), tile_cd, 0.0)
    tiebreak = finite_cd / (jnp.max(finite_cd) + 1.0) * 1e-3  # < votes
    _, tile_buckets = jax.lax.top_k(votes - tiebreak, n_probe)
    window_starts = index.starts[tile_buckets]  # (QT, P) flat offsets
    window_rows = index.bucket_sizes[tile_buckets]  # (QT, P) sizes
    return order, inv, q_sorted, tile_buckets, window_starts, window_rows


def _fused_stats(index: IVFIndex, stats, *, qn: int, k: int, block_q: int,
                 block_c: int, seed_r: bool) -> FusedScanStats:
    """FusedScanStats epilogue from raw kernel stats rows.

    One place turns the (Q, 7) counters into the per-query ledger, shared
    by ``search_ivf_fused`` and the continuous engine so a solo slot's
    ledger is built by the same arithmetic the batch oracle uses (the stat
    columns are integer-valued f32 — sums are exact, so the ledgers compare
    with ``==``)."""
    tr = current_tracer()
    st = np.asarray(stats)
    rows = max(float(st[:, 2].sum()), 1.0)
    # Seeding streams the nearest bucket's int8 codes and k exact rows per
    # query before the kernel launch — count those corpus bytes too.
    d_pad = index.flat_rot.shape[1]
    seed_bytes = (index.capacity * index.qbuckets.shape[2]
                  + 4 * k * d_pad) if seed_r else 0
    # DMA-granular accounting: the demand-paged kernel reports the int8
    # tiles and fp32 slabs it actually shipped from HBM (fetch counters
    # broadcast per query tile; fused_fetch_totals stride-samples them
    # losslessly).  A non-paged pipeline would ship every slab of every
    # scanned tile — that is the skip-rate denominator.
    s1_tiles, s2_slabs = fused_fetch_totals(st, block_q)
    block_d = index.scan_block_d
    fp_itemsize = jnp.dtype(index.flat_rot.dtype).itemsize
    s2_fetched_b, _, s2_skip, s2_total = stage2_fetch_report(
        s1_tiles, s2_slabs, block_c=block_c, d_pad=d_pad, block_d=block_d,
        fp_bytes=fp_itemsize)
    tr.instant("ivf.stage1_dma", tiles=s1_tiles,
               bytes=fetched_tile_bytes(s1_tiles, block_c=block_c,
                                        dims=d_pad, bytes_per_dim=1,
                                        id_bytes=ID_BYTES))
    tr.instant("ivf.stage2", slabs=s2_slabs, bytes=float(s2_fetched_b))
    fetched = fetched_tile_bytes(
        s1_tiles, block_c=block_c, dims=d_pad, bytes_per_dim=1,
        id_bytes=ID_BYTES) + s2_fetched_b
    return FusedScanStats(
        avg_fp_dims=float(st[:, 1].sum()) / rows,
        avg_int8_dims=float(st[:, 0].sum()) / rows,
        rows_per_query=rows / qn,
        bytes_per_query=(float(st[:, 0].sum()) + 4.0 * float(st[:, 1].sum())
                         ) / qn + seed_bytes,
        passed_per_query=float(st[:, 3].sum()) / qn,
        s1_tiles_fetched=s1_tiles,
        s2_slabs_total=s2_total,
        s2_slabs_fetched=s2_slabs,
        s2_skip_rate=s2_skip,
        fetched_bytes_per_query=fetched / qn + seed_bytes,
    )


def search_ivf_fused(
    index: IVFIndex,
    queries: jax.Array,
    *,
    k: int = 10,
    n_probe: int = 8,
    block_q: int = 8,
    block_c: int = 128,
    seed_r: bool = True,
    interpret: bool | None = None,
    use_ref: bool = False,
):
    """IVF search through the fused wave-scan megakernel.

    Queries are grouped into tiles of ``block_q`` by nearest centroid (so a
    tile's queries agree on buckets), each tile probes its ``n_probe`` best
    buckets ranked by the tile-min centroid distance, and one kernel launch
    streams every (tile, probe) bucket window from the CSR flat layout —
    screening, refining, and maintaining the top-K entirely on device.

    Needs ``build_ivf(..., quant="int8")``.  Returns
    (dists (Q, K), ids (Q, K), FusedScanStats).

    Note the bucket semantics differ slightly from ``search_ivf``: probes
    are per *tile*, so a query can scan a neighbour's bucket (extra recall,
    more bytes) or miss its own n-th-choice bucket (tile disagreement —
    mitigated by the nearest-centroid grouping; ``block_q=8`` keeps tiles
    coherent on CPU, 32 is the compiled-mode minimum for int8 tiles).
    """
    if not index.has_fused:
        raise ValueError("search_ivf_fused needs build_ivf(..., quant='int8')")
    # NULL_TRACER by default: every span/instant/fence below is a no-op
    # unless serve/bench installed a recording tracer (repro.obs.trace).
    tr = current_tracer()
    q = queries.astype(jnp.float32)
    q_rot = index.estimator.rotate(q)
    qn = q_rot.shape[0]
    n_probe = min(n_probe, index.n_clusters)

    with tr.span("ivf.route", n_probe=n_probe):
        (order, inv, q_sorted, tile_buckets, window_starts,
         window_rows) = _route_tiles(index, q_rot, n_probe=n_probe,
                                     block_q=block_q)
        q_tiles = (qn + block_q - 1) // block_q
        tr.fence(window_rows)

    with tr.span("ivf.seed", seed_r=seed_r):
        if seed_r:
            # Seed from the tile's best bucket (guaranteed scanned), so
            # the exact-verified candidates re-enter the on-device top-K
            # in wave 0.
            seed_bucket = jnp.repeat(tile_buckets[:, 0], block_q)[:qn]
            r0 = _quant_seed_rsq(index, q_sorted, seed_bucket, k)
        else:
            r0 = jnp.full((qn,), jnp.inf)
        tr.fence(r0)

    with tr.span("ivf.launch", q_tiles=q_tiles):
        top_sq, top_ids, stats = tr.fence(ivf_scan_kernel(
            index.estimator, q_sorted, window_starts, window_rows,
            index.flat_rot, index.flat_codes, index.flat_ids, index.bscales,
            r0, k=k, max_bucket=index.max_bucket, block_q=block_q,
            block_c=block_c, block_d=index.scan_block_d,
            # Build aligns cluster starts to the 128-row grid; any tile
            # width dividing it inherits exact windows.
            starts_aligned=(128 % block_c == 0),
            interpret=interpret, use_ref=use_ref,
        ))
    dists = jnp.sqrt(jnp.maximum(top_sq, 0.0))[inv]
    ids = top_ids[inv]
    fused_stats = _fused_stats(index, stats, qn=qn, k=k, block_q=block_q,
                               block_c=block_c, seed_r=seed_r)
    return dists, ids, fused_stats
