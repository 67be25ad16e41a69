"""Fused IVF wave-scan megakernel with a demand-paged stage 2 (Pallas TPU).

One kernel launch performs the whole IVF probe scan that ``search_ivf``
previously ran as a host-orchestrated gather + vmapped jnp screen:

  * **Gather-free bucket streaming.**  The corpus lives in a flat
    cluster-contiguous layout (``repro.index.ivf`` CSR fields, cluster
    starts aligned to the tile grid).  A scalar-prefetched
    ``(q_tiles, n_probe, cap_tiles)`` offset table names each grid step's
    candidate tile; out-of-span steps of buckets shorter than the largest
    one carry offset ``-1`` and ship **nothing** — the PR-2 automatic
    pipeline re-fetched the sentinel tail once per probe.  The
    ``(Q, cap, D)`` fp32 gather copy the old path materialized per probe
    never exists.
  * **Manually pipelined int8 stream.**  Stage-1 candidate tiles are NOT
    BlockSpec-streamed: the int8 corpus stays HBM-resident
    (``memory_space=ANY``) and the kernel drives a double-buffered
    ``pltpu.make_async_copy`` pipeline itself — the copy of tile t+1 is
    issued before the wait on tile t, so stage-1 DMA overlaps stage-1
    compute exactly like the automatic pipeline, and a step revisiting the
    last *issued* tile (unaligned window overlap — even across intervening
    -1 gap steps) reuses the landed buffer instead of re-fetching it.
  * **Demand-paged fp32 stage 2.**  This is the point of the manual
    pipeline: no fp32 byte moves until stage 1 reports survivors.  The
    fetch is slab-granular — one ``(block_c, block_d)`` fp32 slab per
    checkpoint, issued inside ``@pl.when`` only while
    ``tiles.stage2_need`` says a valid candidate is still active, waited
    on right before that slab's re-screen step.  An all-pruned tile pays
    zero fp32 bytes; a tile whose survivors retire at the first checkpoint
    (the common case once r tightens) pays one slab instead of the whole
    row — under the PR-2 automatic pipeline the compiler shipped every
    fp32 tile from HBM and ``@pl.when`` only skipped the compute.  Stage 2
    is single-shot (no double buffer): whether slab s+1 is needed is only
    known after slab s's checkpoint, so there is nothing to overlap — the
    int8 prefetch of the next tile keeps the pipe busy instead.
  * **int8×int8 MXU prefilter.**  Stage 1 screens each candidate tile with
    the quantized lower bound computed from a true int8×int8
    ``dot_general`` accumulating in **int32** on the MXU.  Per-*block*
    scales (``repro.quant.scalar.fit_block_scales``) make the dequantize a
    single scalar multiply per (tile, dim-block); queries are int8 too
    (per-(query, block) scales fitted from the query itself, so they never
    clip), and the error band adds the query and corpus halves:
    ``||q-o||_d >= ||q'-o'||_d - E_c(d) - E_q(d)``.
  * **On-device top-K.**  The running top-K and the DCO threshold r² live
    in VMEM scratch and carry across the (probe, candidate-tile) grid axes,
    so r tightens between waves without a host round-trip or an HBM
    (Q, N)-shaped intermediate.

Soundness: stage 1 prunes only candidates whose *lower bound* already fails
the DADE test, so every pruned row would also have been rejected by the
fp32 screen at the same checkpoint — the ``passed`` set equals the fp32
screen's (no false prunes; see ``repro.quant.scalar`` for the bound).
Fetch elision is result-invariant by the same argument: a slab is skipped
only when no *valid* candidate is still active, rows that stay active
through slab s are guaranteed slab s was fetched (their distances are
exact), and rows that compute against a stale slab are either already
retired or invalid — masked out of ``passed``/``stats`` before anything
escapes the kernel.  Results stay bit-identical to the PR-2 kernel and to
``ref.ivf_scan_ref``.

Byte accounting: ``stats`` carries DMA-granular fetch counters next to the
semantic dims-consumed columns, so wrappers report *fetched* bytes (what
HBM actually shipped) as well as the dims-consumed quantity fig6/fig7
track for the host engines.  Tile shapes: compiled mode needs int8 tiles
of at least (32, 128), so ``block_q >= 32`` and ``D_pad`` a multiple of
128 on real TPUs (``repro.kernels.ops.min_block_q``); interpret mode (CPU
tests) accepts smaller tiles.  The IVF route keeps 128-row candidate
tiles; the flat route, whose every tile is a real contiguous row range,
launches the wider shape ``ops.flat_tile_shape`` picks under
``VMEM_LIMIT_BYTES``.

The per-tile stage/merge helpers live in ``repro.kernels.tiles`` and are
shared with the ``ref.py`` oracle, so kernel-vs-oracle parity — including
the fetch counters — is structural, not statistical.

Scratch layout (the manual pipeline's working set):

    codes_buf (2, BC, D) int8  — stage-1 double buffer (slots alternate)
    rows_buf  (BC, D) fp       — stage-2 landing buffer, filled slab-wise
    slot_s    (1, 2) i32 SMEM  — [0]: codes_buf slot holding this step's
                                 tile; [1]: offset of the last tile whose
                                 DMA was issued (-1 before the first) — the
                                 cross-gap reuse cursor: a real step whose
                                 offset matches it re-screens the landed
                                 buffer even when -1 gap steps intervened
                                 (a window ending in gap steps used to
                                 force a refetch of a still-resident tile)
    sem8      DMA (2,)         — one semaphore per stage-1 slot
    sem32     DMA ()           — stage-2 slab semaphore (sequential)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import ANY_MEMSPACE, CompilerParams
# Re-exported convenience: these helpers lived here before moving to the
# shared tiles module (PR 3 satellite).  NOTE stage2_tile's signature
# changed with demand paging (a required ``valid`` mask; returns a 4-tuple
# ending in the slab-fetch count) — import from repro.kernels.tiles for
# the canonical API.
from repro.kernels.tiles import (  # noqa: F401
    dup_mask, merge_topk_tile, stage1_tile, stage2_need, stage2_slab,
    stage2_tile,
)

__all__ = ["ivf_scan_kernel_call", "STATS_COLS", "VMEM_LIMIT_BYTES",
           "vmem_bytes", "stage1_tile", "stage2_tile", "merge_topk_tile",
           "dup_mask"]

# stats columns: semantic dims-consumed accounting (0-3, unchanged since
# PR 2) + DMA-granular fetch counters (4-5, tile-level, broadcast to every
# query row of the tile so the oracle can assert them elementwise).
STATS_COLS = (
    "int8_dims",        # 0: int8 dims consumed (retirement checkpoints)
    "fp32_dims",        # 1: fp32 dims consumed by stage-2 survivors
    "rows_scanned",     # 2: valid candidate rows screened
    "rows_passed",      # 3: rows surviving the full screen
    "s2_slabs_fetched",  # 4: fp32 (BC, block_d) slabs actually DMA'd
    "s1_tiles_fetched",  # 5: int8 tiles actually DMA'd (fresh real offsets)
)

# Scoped VMEM every launch asks for: TPU v5e's default scoped limit, named
# so that the tile rule (``ops.flat_tile_shape``) and the compiler share it.
VMEM_LIMIT_BYTES = 16 << 20
# (BQ, BC) f32 temporaries live at once across stage 1, stage 2 and the
# top-K merge.  Mosaic asked for 21.2 MiB at (64, 4096) with bf16 256-d
# rows; ``vmem_bytes`` gives 24 MiB there.
_TILE_TEMPS = 16


def vmem_bytes(block_q: int, block_c: int, dim: int, row_dtype) -> int:
    """Upper estimate of one launch's VMEM working set: the int8 double
    buffer, the stage-2 landing buffer and its f32 upcast (counted over the
    whole row, not one slab), and the (block_q, block_c) f32 temporaries."""
    row_bytes = jnp.dtype(row_dtype).itemsize
    return (block_c * dim * (2 + row_bytes + 4)
            + _TILE_TEMPS * block_q * block_c * 4)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _kernel(
    # scalar prefetch
    offs_ref,  # (q_tiles, P, T) i32 — candidate-tile offset per grid step;
    # out-of-span steps of short buckets are -1 (skipped entirely)
    # inputs
    qcodes_ref,  # (QT, D) int8 query codes
    q_ref,  # (QT, D) f32 exact rotated queries
    qscales_ref,  # (QT, S) f32 per-query block scales
    rsq0_ref,  # (QT, 1) f32 seeded initial thresholds
    top0_sq_ref,  # (QT, K) f32 seeded top-K window (inf = empty)
    top0_ids_ref,  # (QT, K) i32 seeded top-K ids (-1 = empty)
    codes_hbm,  # (N_pad, D) int8 flat corpus codes — HBM-resident (ANY)
    rows_hbm,  # (N_pad, D) fp flat corpus rows — HBM-resident (ANY)
    ids_ref,  # (1, CT) i32 corpus row ids, -1 for tail padding
    bscales_ref,  # (1, S) f32 corpus block scales
    eps_ref,  # (1, S) f32
    scale_ref,  # (1, S) f32
    # outputs
    top_sq_ref,  # (QT, K) f32
    top_ids_ref,  # (QT, K) i32
    stats_ref,  # (QT, 6) f32 — see STATS_COLS
    # scratch
    top_sq_s,  # (QT, K) f32 VMEM
    top_ids_s,  # (QT, K) i32 VMEM
    rsq_s,  # (QT, 1) f32 VMEM
    stats_s,  # (QT, 6) f32 VMEM
    codes_buf,  # (2, CT, D) int8 VMEM — stage-1 double buffer
    rows_buf,  # (CT, D) fp VMEM — stage-2 landing buffer
    slot_s,  # (1, 2) i32 SMEM — [slot cursor, last issued offset]
    sem8,  # DMA (2,) — stage-1 per-slot semaphores
    sem32,  # DMA () — stage-2 slab semaphore
    *,
    num_probes: int,
    cap_tiles: int,
    k: int,
    block_c: int,
    block_d: int,
    slack: float,
):
    i = pl.program_id(0)
    p = pl.program_id(1)
    t = pl.program_id(2)
    step = p * cap_tiles + t
    num_steps = num_probes * cap_tiles

    def off_at(s):
        return offs_ref[i, s // cap_tiles, jax.lax.rem(s, cap_tiles)]

    def codes_dma(slot, s):
        return pltpu.make_async_copy(
            codes_hbm.at[pl.ds(off_at(s) * block_c, block_c), :],
            codes_buf.at[slot],
            sem8.at[slot],
        )

    off = off_at(step)
    real = off >= 0  # -1 steps (out-of-span window tail) ship nothing

    @pl.when(step == 0)
    def _init():
        # The top-K window seeds from the caller (inf/-1 = empty): a
        # chunked launch sequence resumes the window the previous chunk
        # returned, keeping split probe plans bit-identical to one launch.
        top_sq_s[...] = top0_sq_ref[...]
        top_ids_s[...] = top0_ids_ref[...]
        rsq_s[...] = rsq0_ref[...]
        stats_s[...] = jnp.zeros_like(stats_s)
        slot_s[0, 0] = 0
        slot_s[0, 1] = -1  # no tile issued yet

    @pl.when((step == 0) & real)
    def _warmup():
        codes_dma(0, step).start()  # wave 0's tile into slot 0

    cur = slot_s[0, 0]
    # Cross-gap buffer reuse: a real step whose offset equals the last
    # *issued* offset re-screens the tile already landed in ``cur`` — no
    # DMA is started for it and none is waited on.  Comparing against the
    # SMEM cursor instead of the immediately previous step's offset means a
    # window ending in -1 gap steps no longer forces a refetch of a tile
    # that is still resident (unaligned layouts can revisit a tile across
    # a gap); the oracle mirrors the same rule.
    last = slot_s[0, 1]
    fresh = real & (off != last)
    # The tile resident (or inbound) in ``cur`` after this step.
    resident = jnp.where(real, off, last)

    # Issue the NEXT real tile's int8 copy into the other slot before
    # waiting on the current one: the copy overlaps this step's stage-1 and
    # stage-2 work.  At most one stage-1 copy is in flight, so two buffers
    # suffice.  The predicate compares against ``resident`` so the reuse
    # rule and the prefetch rule cannot disagree.
    nxt = jnp.minimum(step + 1, num_steps - 1)
    nxt_fresh = ((step + 1 < num_steps) & (off_at(nxt) >= 0)
                 & (off_at(nxt) != resident))

    @pl.when(nxt_fresh)
    def _prefetch():
        codes_dma(1 - cur, nxt).start()
        slot_s[0, 0] = 1 - cur

    @pl.when(fresh)
    def _land():
        codes_dma(cur, step).wait()

    slot_s[0, 1] = resident

    # Gap steps (real=False) contribute nothing — no DMA was started for
    # them, and running the screen on the stale buffer would only produce
    # all-masked results; skip their compute entirely (the oracle skips
    # these steps the same way).
    @pl.when(real)
    def _screen_tile():
        ids = ids_ref[...]  # (1, CT)
        valid = ids >= 0
        validf = valid.astype(jnp.float32)
        rsq = rsq_s[...]  # frozen for this tile (wave-synchronous semantics)
        eps = eps_ref[0, :]
        scale = scale_ref[0, :]

        active8, d8 = stage1_tile(
            qcodes_ref[...], qscales_ref[...], codes_buf[cur],
            bscales_ref[0, :], eps, scale, rsq, block_d=block_d, slack=slack,
        )
        d8_sum = jnp.sum(d8 * validf, axis=1, keepdims=True)  # (QT, 1)
        nvalid = jnp.broadcast_to(
            jnp.sum(validf, axis=1, keepdims=True), d8_sum.shape)
        zero = jnp.zeros_like(d8_sum)
        one = jnp.ones_like(d8_sum)
        s1_fetched = jnp.where(fresh, one, zero)
        stats_s[...] += jnp.concatenate(
            [d8_sum, zero, nvalid, zero, zero, s1_fetched], axis=1)

        alive = jnp.sum((active8 & valid).astype(jnp.int32))

        @pl.when(alive > 0)
        def _stage2_and_merge():
            q = q_ref[...]
            s_count = q.shape[1] // block_d
            bq = q.shape[0]
            # Progressive demand paging over fp32 dim slabs: slab s is
            # shipped only while a valid candidate is still active
            # (tiles.stage2_need); the screen steps are the shared
            # tiles.stage2_slab, so the oracle replays both the arithmetic
            # and the fetch decisions exactly.  Slabs that are skipped
            # leave stale data in rows_buf — harmless: a row still active
            # at slab s is guaranteed slab s was fetched, and
            # retired/invalid rows are masked out of passed/stats below.
            psum = jnp.zeros((bq, block_c), jnp.float32)
            active = active8
            d32 = jnp.zeros((bq, block_c), jnp.float32)
            slab_cnt = jnp.zeros((), jnp.float32)
            for s in range(s_count):
                need = stage2_need(active, valid)

                @pl.when(need)
                def _fetch_slab(s=s):
                    sdma = pltpu.make_async_copy(
                        rows_hbm.at[pl.ds(off * block_c, block_c),
                                    pl.ds(s * block_d, block_d)],
                        rows_buf.at[:, pl.ds(s * block_d, block_d)],
                        sem32,
                    )
                    sdma.start()
                    sdma.wait()

                slab_cnt = slab_cnt + jnp.where(need, 1.0, 0.0)
                sl = slice(s * block_d, (s + 1) * block_d)
                psum, active, d32_inc = stage2_slab(
                    psum, active, q[:, sl].astype(jnp.float32),
                    rows_buf[:, sl].astype(jnp.float32),
                    eps[s], scale[s], rsq,
                    block_d=block_d, is_last=s == s_count - 1)
                d32 = d32 + d32_inc
            passed = active & (psum <= rsq)
            exact_sq = psum

            ok = passed & valid
            d32_sum = jnp.sum(d32 * validf, axis=1, keepdims=True)
            npass = jnp.sum(ok.astype(jnp.float32), axis=1, keepdims=True)
            z = jnp.zeros_like(d32_sum)
            slabs = jnp.broadcast_to(slab_cnt, d32_sum.shape)
            stats_s[...] += jnp.concatenate([z, d32_sum, z, npass, slabs, z],
                                            axis=1)

            dup = dup_mask(ids, top_ids_s[...], k=k)
            new_sq = jnp.where(ok & ~dup, exact_sq, jnp.inf)
            top_sq, top_ids = merge_topk_tile(
                top_sq_s[...], top_ids_s[...], new_sq, ids, k=k
            )
            top_sq_s[...] = top_sq
            top_ids_s[...] = top_ids
            # Threshold tightens between waves on device — no host
            # round-trip.
            rsq_s[...] = jnp.minimum(rsq_s[...], top_sq[:, k - 1:k])

    @pl.when((p == num_probes - 1) & (t == cap_tiles - 1))
    def _finalize():
        top_sq_ref[...] = top_sq_s[...]
        top_ids_ref[...] = top_ids_s[...]
        stats_ref[...] = stats_s[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_c", "block_d", "cap_tiles",
                     "slack", "interpret"),
)
def ivf_scan_kernel_call(
    tile_offs: jax.Array,  # (q_tiles, P, cap_tiles) i32 per-step offsets
    qcodes: jax.Array,  # (Q, D) int8
    q_rot: jax.Array,  # (Q, D) f32
    qscales: jax.Array,  # (Q, S) f32
    r0_sq: jax.Array,  # (Q,) f32
    top0_sq: jax.Array,  # (Q, K) f32 seeded top-K window (inf = empty)
    top0_ids: jax.Array,  # (Q, K) i32 seeded top-K ids (-1 = empty)
    flat_codes: jax.Array,  # (N_pad, D) int8 cluster-contiguous
    flat_rot: jax.Array,  # (N_pad, D) f32/bf16
    flat_ids: jax.Array,  # (N_pad,) i32, -1 tail padding
    bscales: jax.Array,  # (S,) f32
    eps: jax.Array,  # (S,) f32 blocked table
    scale: jax.Array,  # (S,) f32
    *,
    k: int,
    block_q: int = 32,
    block_c: int = 128,
    block_d: int = 128,
    cap_tiles: int = 1,
    slack: float = 1e-4,
    interpret: bool = False,
):
    """Launch the fused IVF wave scan.  Shapes must be pre-padded:
    Q % block_q == 0, N_pad % block_c == 0, D % block_d == 0, and every
    offset in ``tile_offs`` must be -1 (skipped step) or stay within
    N_pad//block_c (the wrapper in ``repro.kernels.ops`` enforces all of
    this and builds the per-step offset table).  ``flat_codes``/``flat_rot``
    are passed UNBLOCKED — they stay HBM-resident and the kernel pages
    candidate tiles in manually.

    Returns (top_sq (Q, K) f32 ascending, top_ids (Q, K) i32,
    stats (Q, 6) f32 — see ``STATS_COLS``).
    """
    qn, dim = q_rot.shape
    n_pad = flat_rot.shape[0]
    s_count = dim // block_d
    if qn % block_q or n_pad % block_c or dim % block_d:
        raise ValueError(
            f"shapes must be padded: Q={qn}%{block_q}, N={n_pad}%{block_c}, "
            f"D={dim}%{block_d}"
        )
    if flat_codes.dtype != jnp.int8 or qcodes.dtype != jnp.int8:
        raise ValueError("codes must be int8")
    if not interpret and block_d % 128:
        raise ValueError(
            f"compiled lowering needs block_d % 128 == 0 (the demand-paged "
            f"stage-2 slab DMA must land on lane-aligned VMEM windows), got "
            f"{block_d}; use a 128-multiple dimension block or interpret "
            f"mode (ROADMAP records sub-128 slab support as a follow-up)")
    if eps.shape[0] != s_count or bscales.shape[0] != s_count:
        raise ValueError(f"table/scales must have {s_count} block steps")
    if not 1 <= k <= 128:
        raise ValueError(f"k must be in [1, 128], got {k}")
    q_tiles = qn // block_q
    num_probes = tile_offs.shape[1]
    if tile_offs.shape[:1] + tile_offs.shape[2:] != (q_tiles, cap_tiles):
        raise ValueError(
            f"tile_offs is {tile_offs.shape}, need ({q_tiles}, P, {cap_tiles})")

    grid = (q_tiles, num_probes, cap_tiles)
    kernel = functools.partial(
        _kernel, num_probes=num_probes, cap_tiles=cap_tiles, k=k,
        block_c=block_c, block_d=block_d, slack=slack,
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, dim), lambda i, p, t, offs: (i, 0)),
            pl.BlockSpec((block_q, dim), lambda i, p, t, offs: (i, 0)),
            pl.BlockSpec((block_q, s_count), lambda i, p, t, offs: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, p, t, offs: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, p, t, offs: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, p, t, offs: (i, 0)),
            # The candidate streams are NOT pipelined by BlockSpec: the
            # kernel pages them manually (int8 double-buffered, fp32 slabs
            # on demand), so an all-pruned tile never ships fp32 bytes.
            pl.BlockSpec(memory_space=ANY_MEMSPACE),
            pl.BlockSpec(memory_space=ANY_MEMSPACE),
            # ids ride the automatic pipeline (4 B/row); -1 steps clamp to
            # tile 0, which the kernel never reads (gap steps are fully
            # predicated out via ``real``).
            pl.BlockSpec((1, block_c),
                         lambda i, p, t, offs: (0, jnp.maximum(offs[i, p, t], 0))),
            pl.BlockSpec((1, s_count), lambda i, p, t, offs: (0, 0)),
            pl.BlockSpec((1, s_count), lambda i, p, t, offs: (0, 0)),
            pl.BlockSpec((1, s_count), lambda i, p, t, offs: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_q, k), lambda i, p, t, offs: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, p, t, offs: (i, 0)),
            pl.BlockSpec((block_q, len(STATS_COLS)),
                         lambda i, p, t, offs: (i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, len(STATS_COLS)), jnp.float32),
            pltpu.VMEM((2, block_c, dim), jnp.int8),
            pltpu.VMEM((block_c, dim), flat_rot.dtype),
            pltpu.SMEM((1, 2), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out_shapes = (
        jax.ShapeDtypeStruct((qn, k), jnp.float32),
        jax.ShapeDtypeStruct((qn, k), jnp.int32),
        jax.ShapeDtypeStruct((qn, len(STATS_COLS)), jnp.float32),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(
        tile_offs.astype(jnp.int32),
        qcodes,
        q_rot.astype(jnp.float32),
        qscales.astype(jnp.float32),
        r0_sq.reshape(-1, 1).astype(jnp.float32),
        top0_sq.astype(jnp.float32),
        top0_ids.astype(jnp.int32),
        flat_codes,
        flat_rot,  # f32 or bf16 — stage 2 upcasts per block
        flat_ids.reshape(1, -1).astype(jnp.int32),
        bscales.reshape(1, -1).astype(jnp.float32),
        eps.reshape(1, -1).astype(jnp.float32),
        scale.reshape(1, -1).astype(jnp.float32),
    )
