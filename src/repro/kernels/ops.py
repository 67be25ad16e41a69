"""Jit'd public wrappers around the Pallas kernels.

Handles padding to tile boundaries, table resampling to the kernel's
block-checkpoint schedule, and the kernel mode: ``interpret=None`` resolves
through :func:`auto_interpret` (compiled Mosaic lowering on TPU, the Pallas
interpreter elsewhere), so the same call-site code runs in CPU tests and
compiles for TPU.  The serving driver resolves the mode once, passes it
explicitly and reports it.

Shape/alignment contract (every fused-kernel entry point enforces these and
fails fast with the offending value — see ``docs/ARCHITECTURE.md`` for the
rationale behind each):

  * ``block_q >= min_block_q(int8) == 32`` in compiled (non-interpret)
    mode — the int8 sublane floor of the Mosaic tile grid; interpret mode
    accepts any tile.
  * ``block_d % 128 == 0`` in compiled mode — the demand-paged stage-2
    slab DMA must land on lane-aligned VMEM windows.
  * ``block_c >= 32`` for the graph kernel in compiled mode — the int8
    candidate tile's sublane floor (the IVF path's fixed 128 satisfies it
    by construction; the adjacency build pads neighbour blocks up to it).
  * offset tables (``build_window_offsets`` / the beam driver's wave
    offsets) use sentinel ``-1`` for steps that must ship nothing; every
    non-negative offset must stay inside the flat layout's tile count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibration import EpsilonTable
from repro.core.estimators import (
    EPS_DISABLED, Estimator, EstimatorSpec, UnsupportedMethodError,
    blocked_schedule, kernel_spec,
)
from repro.kernels import dade_dco as _dade
from repro.kernels import graph_scan as _graph_scan
from repro.kernels import ivf_scan as _ivf_scan
from repro.kernels import quant_dco as _quant
from repro.kernels import ref as _ref
from repro.quant.scalar import cum_err_sq, quantize_queries_block

__all__ = [
    "dco_screen_kernel", "quant_screen_kernel", "ivf_scan_kernel",
    "graph_scan_kernel", "ivf_cap_tiles", "build_window_offsets",
    "block_table", "on_tpu", "auto_interpret", "auto_block_q", "min_block_q",
    "flat_tile_shape", "fused_fetch_totals",
    "graph_vis_words", "unpack_vis", "pow2_bucket", "pad_live_rows",
    "EstimatorSpec", "UnsupportedMethodError", "kernel_spec", "EPS_DISABLED",
]

# Minimum second-to-minor tile dimension (sublane count) per operand byte
# width for COMPILED Mosaic lowering; interpret mode accepts anything.
_SUBLANE_MIN = {1: 32, 2: 16, 4: 8}


def min_block_q(dtype=jnp.int8) -> int:
    """Minimum query-tile rows for compiled-mode lowering.

    The fused kernel's narrowest operand sets the sublane floor: int8 tiles
    must be at least (32, 128) on real TPUs, so any launch carrying int8
    codes needs ``block_q >= min_block_q(jnp.int8) == 32``.  Tests use this
    to auto-select a legal tile instead of hardcoding the constraint."""
    return _SUBLANE_MIN.get(jnp.dtype(dtype).itemsize, 8)


def graph_vis_words(n_nodes: int) -> int:
    """Packed visited-bitmap width (int32 words) for ``n_nodes`` graph
    nodes: ``ceil(n_nodes / 32)`` rounded up to the 128-lane grid so the
    ``(1, W)`` bitmap blocks lower compiled.  Sharded engines size the
    bitmap with the GLOBAL node count — every shard marks the same global
    id space (bit ``vis_base + local_offset``)."""
    words = (max(n_nodes, 1) + 31) // 32
    return (words + 127) // 128 * 128


def unpack_vis(vis, n_nodes: int):
    """(q_tiles, W) packed int32 bitmap -> (q_tiles, n_nodes) bool mask.

    Host-side helper for the beam driver's frontier selection: the kernel
    owns the marking, the host only *reads* the returned bitmap."""
    vis = np.asarray(vis, np.int32)
    bits = (vis[:, :, None] >> np.arange(32, dtype=np.int32)) & 1
    return bits.reshape(vis.shape[0], -1)[:, :n_nodes].astype(bool)


def pack_vis_ranges(n_nodes: int, ranges) -> np.ndarray:
    """(W,) packed int32 bitmap with every node in ``ranges`` (an iterable
    of (base, count) node ranges) set — the tombstone mask of degraded-mode
    serving.  OR-ing it into a wave state's visited bitmap makes those
    nodes "pre-visited": frontier selection never proposes them, so the
    kernel never expands a dead shard's adjacency.  Bit layout matches
    ``unpack_vis`` (bit ``v % 32`` of word ``v // 32``); the kernel's own
    OR-marking composes with pre-set bits unchanged."""
    words = np.zeros((graph_vis_words(n_nodes),), np.uint32)
    for b, c in ranges:
        b, c = int(b), int(c)
        if c < 0 or b < 0 or b + c > n_nodes:
            raise ValueError(
                f"tombstone range [{b}, {b + c}) outside corpus "
                f"[0, {n_nodes})")
        v = np.arange(b, b + c)
        np.bitwise_or.at(words, v // 32,
                         np.uint32(1) << (v % 32).astype(np.uint32))
    return words.view(np.int32)


def fused_fetch_totals(stats, block_q: int):
    """(s1_tiles_fetched, s2_slabs_fetched) totals from fused-scan stats.

    The kernel broadcasts its tile-level DMA counters (stats columns 4-5,
    see ``ivf_scan.STATS_COLS``) to every query row of the tile, so the
    first row of each query tile carries the exact per-tile totals —
    stride-sampling is lossless even after the wrapper crops pad queries
    (each tile keeps at least its first row)."""
    st = np.asarray(stats)
    first = st[::block_q]
    return float(first[:, 5].sum()), float(first[:, 4].sum())


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= ``n`` (minimum 1) — the recompile-bounding
    bucket grid.  Launch dimensions that vary per wave (live-set tile
    counts, frontier step counts) round up to it so a serving run compiles
    at most ``log2(max)`` shapes per dimension instead of one per value."""
    if n < 1:
        raise ValueError(f"pow2_bucket needs n >= 1, got {n}")
    return 1 << (int(n) - 1).bit_length()


def pad_live_rows(x, live_rows: int, bucket_rows: int, *, fill):
    """Ragged live-set padding guard: pad the stacked live-slot rows of a
    continuous-batch launch up to the pow2 bucket, failing fast on the two
    silent-corruption hazards — a stack that disagrees with the declared
    live count (stale slot rows would ride into the kernel as if live) and
    a non-pow2 bucket (which defeats the recompile bound).  Pad rows carry
    ``fill``, the same inert value the batch path pads with, so the kernel
    prunes them at the first checkpoint."""
    x = np.asarray(x)
    if x.shape[0] != live_rows:
        raise ValueError(
            f"live-set stack has {x.shape[0]} rows, caller declared "
            f"{live_rows} live — refusing to launch stale slot rows")
    if bucket_rows < live_rows:
        raise ValueError(
            f"bucket of {bucket_rows} rows cannot hold {live_rows} live rows")
    if bucket_rows & (bucket_rows - 1):
        raise ValueError(
            f"bucket_rows={bucket_rows} is not a power of two — the "
            f"recompile bound needs pow2_bucket sizing")
    if bucket_rows == live_rows:
        return x
    pad = np.full((bucket_rows - live_rows,) + x.shape[1:], fill, x.dtype)
    return np.concatenate([x, pad], axis=0)


def ivf_cap_tiles(max_bucket: int, block_c: int, *, starts_aligned: bool) -> int:
    """Candidate tiles per probe window.  Aligned cluster starts (the
    build-time CSR layout) need exactly ceil(max_bucket / block_c); unaligned
    offsets round down to the tile grid, so the window grows by one tile of
    slack to keep covering the whole bucket."""
    if starts_aligned:
        return max((max_bucket + block_c - 1) // block_c, 1)
    return max((max_bucket + 2 * block_c - 2) // block_c, 1)


def build_window_offsets(window_starts, window_rows, *, block_c: int,
                         cap_tiles: int, n_pad: int):
    """(QT, P) bucket row starts/sizes -> (QT, P, cap_tiles) per-step tile
    offsets for the fused kernel's manual DMA stream.

    Step t of a window points at its bucket's t-th candidate tile while
    t < span (the tiles the bucket actually occupies, round-down slack
    included) and carries ``-1`` otherwise — the demand-paged kernel ships
    nothing for those steps (the PR-2 BlockSpec pipeline re-fetched the
    sentinel tail tile once per probe), so short buckets cost their own
    rows, not ``cap_tiles`` worth."""
    starts = window_starts.astype(jnp.int32)
    rows = window_rows.astype(jnp.int32)
    base = starts // block_c
    span = (starts % block_c + rows + block_c - 1) // block_c  # tiles used
    t_idx = jnp.arange(cap_tiles, dtype=jnp.int32)[None, None, :]
    max_tile = n_pad // block_c - 1
    return jnp.where(t_idx < span[:, :, None],
                     jnp.clip(base[:, :, None] + t_idx, 0, max_tile),
                     jnp.int32(-1))

_PAD_SENTINEL = 1e18  # huge-but-finite: pad rows prune at the first block


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def auto_interpret() -> bool:
    """Kernel mode for a caller that passes ``interpret=None``: compiled
    Mosaic lowering on TPU, the Pallas interpreter on any other backend."""
    return not on_tpu()


def auto_block_q(interpret: bool) -> int:
    """Query-tile rows for a kernel mode: the int8 sublane floor when the
    kernels compile, 8 in interpret mode (tile coherence beats lane
    occupancy there)."""
    return 8 if interpret else min_block_q(jnp.int8)


# Candidate-tile widths the flat route tries, widest first.  On one TPU v5e
# at 1M x 256 bf16 rows and 64-row batches a call took 29.9, 19.0, 11.8 and
# 9.0 ms at 256, 512, 1024 and 2048 rows (66.2 ms at the IVF route's
# (32, 128)); 4096 does not fit the scoped VMEM.
FLAT_BLOCK_C = (2048, 1024, 512, 256, 128)


def flat_tile_shape(q: int, wave: int, d_pad: int, row_dtype, *,
                    interpret: bool = False) -> tuple[int, int]:
    """(block_q, block_c) of the flat route's fused step.

    Every tile of a flat wave is a real, contiguous row range, so a grid
    step can screen far more than the IVF route's 128-row tile: the fixed
    work a step pays (stage-2 entry, slab DMA, top-K merge) then comes
    once per wide tile.  ``block_q`` is the whole batch when it is a
    multiple of the mode's query tile (``auto_block_q``) and fits
    ``ivf_scan.VMEM_LIMIT_BYTES``, else the largest such multiple that
    divides ``q`` and fits; ``block_c`` is the widest of ``FLAT_BLOCK_C``
    that divides ``wave`` and fits beside it.
    """
    granule = auto_block_q(interpret)

    def fits(bq, bc):
        return (_ivf_scan.vmem_bytes(bq, bc, d_pad, row_dtype)
                <= _ivf_scan.VMEM_LIMIT_BYTES)

    block_q = next((bq for bq in range(q - q % granule, 0, -granule)
                    if q % bq == 0 and fits(bq, FLAT_BLOCK_C[-1])), None)
    if block_q is None:
        raise ValueError(f"query_batch {q} has no divisor that is a multiple "
                         f"of the {granule}-row query tile and fits VMEM")
    block_c = next((bc for bc in FLAT_BLOCK_C
                    if wave % bc == 0 and fits(block_q, bc)), None)
    if block_c is None:
        raise ValueError(f"wave {wave} is not a multiple of "
                         f"{FLAT_BLOCK_C[-1]} rows that fit VMEM")
    return block_q, block_c


def block_table(table: EpsilonTable, dim: int, block_d: int):
    """Resample an EpsilonTable onto the kernel's block grid.

    The kernel checkpoints at d = DB, 2DB, ..., D_pad.  For each checkpoint we
    take the table entry at the largest calibrated dim <= checkpoint (so the
    test applied is one the calibration actually covered; conservative).
    Checkpoints BELOW the first calibrated dim carry the ``EPS_DISABLED``
    sentinel — the method never calibrated a test there, so the kernel must
    not invent one (the single-checkpoint FDScanning table under a small
    block_d keeps the paged pipeline but screens only at the terminal
    retire).  Checkpoints beyond the true D (zero-padded dims) reuse the
    final exact entry (eps=0, scale=1) — padded dims add zero.

    Thin jnp adapter over :func:`repro.core.estimators.blocked_schedule`
    (the single source of the resampling rule — the numpy conformance
    references use it directly).
    """
    eps, scale, eps_lo, d_pad = blocked_schedule(table, dim, block_d)
    return (
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(scale, jnp.float32),
        d_pad,
        jnp.asarray(eps_lo, jnp.float32),
    )


def _pad_axis(x: jax.Array, axis: int, to: int, value: float) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % to
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_c", "block_d", "interpret", "use_ref"),
)
def _call(q, c, eps, scale, r_sq, block_q, block_c, block_d, interpret, use_ref):
    if use_ref:
        return _ref.dade_dco_ref(q, c, eps, scale, r_sq, block_d=block_d)
    return _dade.dade_dco_kernel_call(
        q, c, eps, scale, r_sq,
        block_q=block_q, block_c=block_c, block_d=block_d, interpret=interpret,
    )


def dco_screen_kernel(
    estimator: Estimator,
    q_rot: jax.Array,  # (Q, D) rotated queries
    cands_rot: jax.Array,  # (N, D) rotated candidates
    r_sq: jax.Array,  # (Q,)
    *,
    block_q: int = 128,
    block_c: int = 128,
    block_d: int = 128,
    interpret: bool | None = None,
    use_ref: bool = False,
):
    """Public entry: pads, resamples the table, launches the kernel.

    ``interpret=None`` auto-selects: real lowering on TPU, interpret on CPU.
    Returns (est_sq (Q,N) f32, passed (Q,N) bool, dims_used (Q,N) i32),
    cropped back to the caller's shapes.
    """
    if interpret is None:
        interpret = auto_interpret()
    qn, dim = q_rot.shape
    n = cands_rot.shape[0]

    spec = kernel_spec(estimator, dim, block_d)
    eps, scale = spec.eps, spec.scale
    q = _pad_axis(q_rot.astype(jnp.float32), 1, block_d, 0.0)
    c = _pad_axis(cands_rot.astype(jnp.float32), 1, block_d, 0.0)
    q = _pad_axis(q, 0, block_q, 0.0)
    c = _pad_axis(c, 0, block_c, _PAD_SENTINEL)
    r = _pad_axis(r_sq.astype(jnp.float32), 0, block_q, 0.0)

    est_sq, passed, dims_used = _call(
        q, c, eps, scale, r, block_q, block_c, block_d, interpret, use_ref
    )
    return (
        est_sq[:qn, :n],
        passed[:qn, :n].astype(bool),
        dims_used[:qn, :n],
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_c", "block_d", "slack", "interpret", "use_ref"),
)
def _quant_call(q, codes, scales, eps, scale, ecum, r_sq, block_q, block_c,
                block_d, slack, interpret, use_ref):
    if use_ref:
        return _ref.quant_dco_ref(
            q, codes, scales, eps, scale, ecum, r_sq,
            block_d=block_d, slack=slack,
        )
    return _quant.quant_dco_kernel_call(
        q, codes, scales, eps, scale, ecum, r_sq,
        block_q=block_q, block_c=block_c, block_d=block_d, slack=slack,
        interpret=interpret,
    )


def quant_screen_kernel(
    estimator: Estimator,
    q_rot: jax.Array,  # (Q, D) rotated fp32 queries
    codes: jax.Array,  # (N, D) int8 corpus codes
    scales: jax.Array,  # (D,) per-dimension quantization scales
    r_sq: jax.Array,  # (Q,)
    *,
    block_q: int = 128,
    block_c: int = 128,
    block_d: int = 128,
    slack: float = 1e-4,
    interpret: bool | None = None,
    use_ref: bool = False,
):
    """Public entry for the int8 lower-bound prefilter (stage 1).

    Pads to tile boundaries, resamples the epsilon table onto the block
    grid, derives the cumulative quantization-error band E(d) from the
    scales, and launches the kernel (interpret on CPU).  Returns
    (lb_sq (Q,N) f32, pruned (Q,N) bool, lb_dims (Q,N) i32), cropped.
    Padded dimensions carry zero codes AND zero scales, so they add nothing
    to either the distance or the error band.
    """
    if interpret is None:
        interpret = auto_interpret()
    qn, dim = q_rot.shape
    n = codes.shape[0]

    spec = kernel_spec(estimator, dim, block_d)
    eps, scale = spec.eps, spec.scale
    s_count = spec.s_steps
    sc = _pad_axis(scales.astype(jnp.float32), 0, block_d, 0.0)
    ecum = jnp.sqrt(cum_err_sq(sc, (jnp.arange(s_count) + 1) * block_d))

    q = _pad_axis(q_rot.astype(jnp.float32), 1, block_d, 0.0)
    q = _pad_axis(q, 0, block_q, 0.0)
    c = _pad_axis(codes, 1, block_d, 0)
    c = _pad_axis(c, 0, block_c, 0)
    r = _pad_axis(r_sq.astype(jnp.float32), 0, block_q, 0.0)

    lb_sq, pruned, lb_dims = _quant_call(
        q, c, sc, eps, scale, ecum, r, block_q, block_c, block_d, slack,
        interpret, use_ref,
    )
    return (
        lb_sq[:qn, :n],
        pruned[:qn, :n].astype(bool),
        lb_dims[:qn, :n],
    )


def _ivf_scan_call(tile_offs, qcodes, q, qscales, r0, top0_sq, top0_ids,
                   flat_codes, flat_rot, flat_ids, bscales, eps, scale, k,
                   block_q, block_c, block_d, cap_tiles, slack, interpret,
                   use_ref):
    if use_ref:
        # The oracle replays the grid with host loops (concrete offsets),
        # so it runs eagerly — test/debug path only.
        return _ref.ivf_scan_ref(
            tile_offs, qcodes, q, qscales, r0, top0_sq, top0_ids,
            flat_codes, flat_rot, flat_ids, bscales, eps, scale, k=k,
            block_q=block_q, block_c=block_c, block_d=block_d,
            cap_tiles=cap_tiles, slack=slack,
        )
    return _ivf_scan.ivf_scan_kernel_call(
        tile_offs, qcodes, q, qscales, r0, top0_sq, top0_ids, flat_codes,
        flat_rot, flat_ids, bscales, eps, scale, k=k, block_q=block_q,
        block_c=block_c, block_d=block_d, cap_tiles=cap_tiles, slack=slack,
        interpret=interpret,
    )


def ivf_scan_kernel(
    estimator: Estimator,
    q_rot: jax.Array,  # (Q, D) rotated fp32 queries, tile-grouped by caller
    window_starts: jax.Array,  # (ceil(Q/block_q), P) i32 flat ROW offsets
    window_rows: jax.Array,  # (ceil(Q/block_q), P) i32 bucket sizes
    flat_rot: jax.Array,  # (N_pad, D_pad) f32 cluster-contiguous corpus
    flat_codes: jax.Array,  # (N_pad, D_pad) int8 per-block codes
    flat_ids: jax.Array,  # (N_pad,) i32, -1 tail padding
    bscales: jax.Array,  # (S,) f32 corpus per-block scales
    r0_sq: jax.Array,  # (Q,) f32 seeded initial squared thresholds
    top0_sq: jax.Array | None = None,  # (Q, K) f32 seeded top-K window
    top0_ids: jax.Array | None = None,  # (Q, K) i32 seeded top-K ids
    *,
    k: int,
    max_bucket: int,
    block_q: int = 32,
    block_c: int = 128,
    block_d: int = 128,
    starts_aligned: bool = False,
    slack: float = 1e-4,
    interpret: bool | None = None,
    use_ref: bool = False,
):
    """Public entry for the fused IVF wave scan.

    The caller (``repro.index.ivf.search_ivf_fused``) owns query→tile
    grouping and probe selection; this wrapper owns padding, the blocked
    epsilon table, per-(query, block) int8 query quantization, and the
    row→tile offset table.  ``window_starts[i, p]`` / ``window_rows[i, p]``
    are the flat row offset and size of the p-th bucket probed by query
    tile i; the grid reserves ``ivf_cap_tiles(max_bucket, block_c, ...)``
    steps per window but short buckets mark their out-of-span steps -1
    (``build_window_offsets``) and the kernel ships nothing for them, so
    each probe costs its own bucket's rows.  ``starts_aligned`` declares that every window start
    is already a multiple of ``block_c`` (the aligned CSR build layout) —
    windows then cover exactly their bucket; otherwise one slack tile
    absorbs the round-down, and rows pulled in from a neighbouring cluster
    are real candidates (screened soundly, counted in the byte stats).

    The fp32 corpus is handed to the kernel UNBLOCKED: the demand-paged
    megakernel keeps it HBM-resident and fetches a (block_c, D) landing
    block only for tiles with stage-1 survivors, so stats columns 4-5
    (``ivf_scan.STATS_COLS``) count the fp32/int8 tiles actually DMA'd —
    ``fused_fetch_totals`` aggregates them for byte accounting.

    Returns (top_sq (Q, K) ascending, top_ids (Q, K), stats (Q, 7) f32 =
    [int8 dims, fp32 dims, rows scanned, passed rows, s2 tiles fetched,
    s1 tiles fetched, merge rounds]), cropped to Q.
    """
    if interpret is None:
        interpret = auto_interpret()
    if not interpret and not use_ref and block_q < min_block_q(jnp.int8):
        raise ValueError(
            f"compiled lowering needs block_q >= {min_block_q(jnp.int8)} "
            f"(int8 sublane minimum), got {block_q}; interpret mode accepts "
            f"smaller tiles")
    if not interpret and not use_ref and block_d % 128:
        raise ValueError(
            f"compiled lowering needs block_d % 128 == 0 (the demand-paged "
            f"stage-2 slab DMA must land on lane-aligned VMEM windows), got "
            f"{block_d}; build the index with scan_block_d=128 or run "
            f"interpret mode")
    qn, dim = q_rot.shape
    n_pad, d_pad = flat_rot.shape
    if d_pad % block_d or bscales.shape[0] != d_pad // block_d:
        raise ValueError(
            f"flat corpus dim {d_pad} must be a multiple of block_d "
            f"{block_d} with one block scale per block")
    if n_pad % block_c:
        raise ValueError(f"flat corpus rows {n_pad} % block_c {block_c} != 0")
    cap_tiles = ivf_cap_tiles(max_bucket, block_c, starts_aligned=starts_aligned)
    if cap_tiles > n_pad // block_c:
        raise ValueError("flat corpus tail padding too small for max_bucket")

    spec = kernel_spec(estimator, dim, block_d)
    eps, scale = spec.eps, spec.scale
    if spec.d_pad != d_pad:
        raise ValueError(
            f"blocked table spans {spec.d_pad} dims, flat corpus has {d_pad}")

    q = _pad_axis(q_rot.astype(jnp.float32), 1, block_d, 0.0)
    q = _pad_axis(q, 0, block_q, 0.0)
    qcodes, qscales = quantize_queries_block(q, block_d)
    r0 = _pad_axis(r0_sq.astype(jnp.float32), 0, block_q, 0.0)
    # Optional top-K window seeds (inf/-1 = empty, the pre-seeded default):
    # a chunked probe plan resumes the window the previous launch returned,
    # staying bit-identical to the single-launch scan.  Pad rows seed empty
    # like the r²=0 pad rows — they prune instantly either way.
    if top0_sq is None:
        t0_sq = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
        t0_ids = jnp.full((q.shape[0], k), -1, jnp.int32)
    else:
        t0_sq = _pad_axis(top0_sq.astype(jnp.float32), 0, block_q, jnp.inf)
        t0_ids = _pad_axis(top0_ids.astype(jnp.int32), 0, block_q, -1)

    tile_offs = build_window_offsets(
        window_starts, window_rows, block_c=block_c, cap_tiles=cap_tiles,
        n_pad=n_pad)

    top_sq, top_ids, stats = _ivf_scan_call(
        tile_offs, qcodes, q, qscales, r0, t0_sq, t0_ids, flat_codes,
        flat_rot, flat_ids, bscales, eps, scale, k, block_q, block_c,
        block_d, cap_tiles, slack, interpret, use_ref,
    )
    return top_sq[:qn], top_ids[:qn], stats[:qn]


def _graph_scan_call(step_offs, qcodes, q, qscales, top0_sq, top0_ids, r0,
                     vis0, adj_codes, adj_rot, adj_ids, bscales, eps, scale,
                     vis_base, ef, thresh_col, block_q, block_c, block_d,
                     slack, tighten, interpret, use_ref):
    if use_ref:
        # The oracle replays the grid with host loops (concrete offsets),
        # so it runs eagerly — test/debug path and the host beam engine.
        return _ref.graph_scan_ref(
            step_offs, qcodes, q, qscales, top0_sq, top0_ids, r0, vis0,
            adj_codes, adj_rot, adj_ids, bscales, eps, scale, vis_base,
            ef=ef, thresh_col=thresh_col, block_q=block_q, block_c=block_c,
            block_d=block_d, slack=slack, tighten=tighten,
        )
    return _graph_scan.graph_scan_kernel_call(
        step_offs, qcodes, q, qscales, top0_sq, top0_ids, r0, vis0,
        adj_codes, adj_rot, adj_ids, bscales, eps, scale, vis_base, ef=ef,
        thresh_col=thresh_col, block_q=block_q, block_c=block_c,
        block_d=block_d, slack=slack, tighten=tighten, interpret=interpret,
    )


def graph_scan_kernel(
    estimator: Estimator,
    q_rot: jax.Array,  # (Q, D) rotated fp32 queries, tile-grouped by caller
    step_offs: jax.Array,  # (ceil(Q/block_q), steps) i32 TILE offsets, -1 skip
    top0_sq: jax.Array,  # (Q, EF) f32 beam window carried across waves
    top0_ids: jax.Array,  # (Q, EF) i32
    r0_sq: jax.Array,  # (Q,) f32 thresholds carried across waves
    adj_rot: jax.Array,  # (N_adj, D_pad) f32 adjacency-flat neighbour rows
    adj_codes: jax.Array,  # (N_adj, D_pad) int8 per-block codes
    adj_ids: jax.Array,  # (N_adj,) i32, -1 per-block padding
    bscales: jax.Array,  # (S,) f32 corpus per-block scales
    vis0: jax.Array | None = None,  # (q_tiles, W) i32 packed visited bitmap
    *,
    vis_base: int | jax.Array = 0,  # global node id of local tile 0
    # (shard base; a traced scalar inside the shard_map'd wave step)
    vis_nodes: int | None = None,  # global node count the bitmap must cover
    ef: int,
    thresh_col: int | None = None,
    block_q: int = 8,
    block_c: int = 32,
    block_d: int = 32,
    slack: float = 1e-4,
    tighten: bool = True,
    interpret: bool | None = None,
    use_ref: bool = False,
):
    """Public entry for one fused graph beam-scan wave.

    The caller (``repro.index.graph``'s beam driver) owns the frontier: it
    writes one expanded node's tile offset per step of ``step_offs`` (node
    v's neighbour block is tile v of the adjacency-flat layout, so offsets
    ARE node ids when ``block_c == adj_block``) and sentinel ``-1`` for
    steps past a tile's frontier — the kernel ships nothing for those.
    This wrapper owns padding, the blocked epsilon table, per-(query,
    block) int8 query quantization, and the visited bitmap's sizing:
    ``vis0=None`` starts an all-clear bitmap sized ``graph_vis_words``
    over ``vis_nodes`` (default: the local tile count) global nodes.
    Under sharded serving ``vis_base`` shifts local tile offsets into the
    global node id space and ``vis_nodes`` is the GLOBAL node count, so
    every shard marks the same bitmap; ``tighten=False`` selects the
    frozen-wave threshold semantics sharded walks need (see
    ``repro.kernels.graph_scan``).

    Shape/alignment contract (module docstring has the full list):
    compiled (non-interpret) mode fails fast unless
    ``block_q >= min_block_q(int8)``, ``block_c >= min_block_q(int8)``
    (both int8 sublane floors) and ``block_d % 128 == 0`` (lane-aligned
    stage-2 slab DMA); every error names the offending value.  ``ef`` is
    the on-device window size (<= 128, the top-K merge bound);
    ``thresh_col`` selects which window column feeds the DCO threshold
    (``k-1`` = the paper's HNSW++-style decoupled threshold, the default
    ``ef-1`` = the coupled HNSW+ variant); queries are
    padded to ``block_q`` rows with inf/-1 window entries and r²=0, so pad
    rows prune instantly and never touch the outputs.

    Returns (top_sq (Q, EF) ascending, top_ids (Q, EF), stats (Q, 7) f32 =
    ``ivf_scan.STATS_COLS``, vis (q_tiles, W) i32), cropped to Q — feed
    top/r²/vis back in to continue the beam next wave (``unpack_vis``
    turns the bitmap into the frontier-selection mask).
    """
    if interpret is None:
        interpret = auto_interpret()
    if not interpret and not use_ref and block_q < min_block_q(jnp.int8):
        raise ValueError(
            f"compiled lowering needs block_q >= {min_block_q(jnp.int8)} "
            f"(int8 sublane minimum), got block_q={block_q}; interpret mode "
            f"accepts smaller tiles")
    if not interpret and not use_ref and block_c < min_block_q(jnp.int8):
        raise ValueError(
            f"compiled lowering needs block_c >= {min_block_q(jnp.int8)} "
            f"(int8 sublane minimum for the adjacency candidate tile), got "
            f"block_c={block_c}; rebuild the graph with adj_block >= "
            f"{min_block_q(jnp.int8)} or run interpret mode")
    if not interpret and not use_ref and block_d % 128:
        raise ValueError(
            f"compiled lowering needs block_d % 128 == 0 (the demand-paged "
            f"stage-2 slab DMA must land on lane-aligned VMEM windows), got "
            f"block_d={block_d}; build the graph with scan_block_d=128 or "
            f"run interpret mode")
    qn, dim = q_rot.shape
    n_adj, d_pad = adj_rot.shape
    if d_pad % block_d or bscales.shape[0] != d_pad // block_d:
        raise ValueError(
            f"adjacency dim {d_pad} must be a multiple of block_d "
            f"{block_d} with one block scale per block")
    if n_adj % block_c:
        raise ValueError(f"adjacency rows {n_adj} % block_c {block_c} != 0")

    spec = kernel_spec(estimator, dim, block_d)
    eps, scale = spec.eps, spec.scale
    if spec.d_pad != d_pad:
        raise ValueError(
            f"blocked table spans {spec.d_pad} dims, adjacency has {d_pad}")

    q = _pad_axis(q_rot.astype(jnp.float32), 1, block_d, 0.0)
    q = _pad_axis(q, 0, block_q, 0.0)
    qcodes, qscales = quantize_queries_block(q, block_d)
    # Pad rows carry an empty window and r²=0: every candidate's lower
    # bound exceeds 0, so they prune at the first checkpoint and their
    # window stays inf/-1 end to end.
    t_sq = _pad_axis(top0_sq.astype(jnp.float32), 0, block_q, jnp.inf)
    t_ids = _pad_axis(top0_ids.astype(jnp.int32), 0, block_q, -1)
    r0 = _pad_axis(r0_sq.astype(jnp.float32), 0, block_q, 0.0)

    q_tiles = q.shape[0] // block_q
    n_tiles = n_adj // block_c
    concrete_base = isinstance(vis_base, (int, np.integer))
    if vis_nodes is None:
        if not concrete_base:
            raise ValueError(
                "a traced vis_base (sharded shard_map step) needs an "
                "explicit vis_nodes (the GLOBAL node count)")
        vis_nodes = int(vis_base) + n_tiles
    if concrete_base and (vis_base < 0 or vis_base + n_tiles > vis_nodes):
        raise ValueError(
            f"vis_base={vis_base} with {n_tiles} local tiles overruns the "
            f"{vis_nodes}-node global bitmap")
    words = graph_vis_words(vis_nodes)
    if vis0 is None:
        vis0 = jnp.zeros((q_tiles, words), jnp.int32)
    elif vis0.shape != (q_tiles, words):
        raise ValueError(
            f"visited bitmap is {vis0.shape}, need ({q_tiles}, {words}) "
            f"(= graph_vis_words({vis_nodes}) words per query tile)")

    if thresh_col is None:
        thresh_col = ef - 1
    top_sq, top_ids, stats, vis = _graph_scan_call(
        step_offs.astype(jnp.int32), qcodes, q, qscales, t_sq, t_ids, r0,
        vis0, adj_codes, adj_rot, adj_ids, bscales, eps, scale, vis_base,
        ef, thresh_col, block_q, block_c, block_d, slack, tighten,
        interpret, use_ref,
    )
    return top_sq[:qn], top_ids[:qn], stats[:qn], vis
