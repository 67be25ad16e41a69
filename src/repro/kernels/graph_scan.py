"""Fused graph beam-scan megakernel (Pallas TPU) — one launch per wave.

``repro.index.graph.search_graph`` walks the proximity graph with a
host-side greedy loop: every expansion gathers one (M, D) fp32 neighbour
block and screens it alone.  This kernel is the graph half of the megakernel
family (``ivf_scan`` is the IVF half): a whole *wave* of frontier
expansions — for a whole query batch — runs as ONE Pallas launch, and the
only host work left is committing frontier/visited updates between waves.

The architecture generalizes ``ivf_scan`` from "probe a static bucket list"
to "probe a data-dependent frontier":

  * **Gather-free adjacency streaming.**  The graph build lays every node's
    neighbour rows out contiguously (the *adjacency-flat* layout: node v's
    neighbours occupy rows ``[v*A, (v+1)*A)`` of ``adj_rot``/``adj_codes``,
    A = ``adj_block`` = the kernel's candidate-tile height).  Expanding
    node v is therefore streaming exactly one tile at offset v — no
    ``(M, D)`` gather copy ever exists, the same trick the IVF CSR layout
    plays with aligned cluster starts.
  * **Frontier-shaped offset table.**  A scalar-prefetched
    ``(q_tiles, steps)`` table names each grid step's candidate tile: the
    host driver writes one expanded node id per real step and ``-1`` for
    the tail of tiles whose frontier produced fewer expansions this wave —
    those steps ship **nothing** (same predication as ``ivf_scan``'s
    out-of-span windows).
  * **Resumable on-device beam.**  The running result window W (size EF)
    and the DCO threshold r² live in VMEM scratch across the wave's steps —
    and, unlike ``ivf_scan``, they are *seeded from inputs*
    (``top0_sq``/``top0_ids``/``rsq0``) and returned at the end, so the
    beam survives across launches: wave n+1 resumes exactly where wave n's
    scratch left off.  This is what makes the kernel wave-synchronous
    rather than one-shot.
  * **Same two-stage screen.**  Stage 1 is the int8×int8 MXU lower-bound
    prefilter, stage 2 the demand-paged fp32 DADE re-screen — both are the
    shared ``repro.kernels.tiles`` helpers, manual-DMA'd exactly like
    ``ivf_scan`` (double-buffered int8 tiles, single-shot fp32 slabs
    fetched only while ``tiles.stage2_need`` reports valid active
    candidates).  An expansion whose whole neighbour block is stage-1
    pruned pays zero fp32 bytes.
  * **Device-side visited bitmap.**  The per-query-tile expansion mask is
    a packed int32 bitmap (bit v set = node v expanded for this tile)
    carried in the wave state exactly like the beam window: seeded from
    ``vis0``, OR-updated in VMEM scratch as each real step expands its
    node, and returned as an output.  The host never marks expansions —
    it only *reads* the returned bitmap when selecting the next frontier,
    which is what lets commits happen per shard per wave under sharded
    serving (the scalar-prefetched ``vis_base`` shifts local tile offsets
    into the global node id space, so every shard marks the same global
    bitmap).  Marking changes no results — re-screens were already sound
    (r never loosens, ``dup_mask`` blocks double admission); the bitmap
    only moves who owns the mask.
  * **Frozen-threshold (sharded) mode.**  ``tighten=False`` skips the
    in-wave r² tightening after each merge: every expansion of the launch
    screens at the carried-in wave-start threshold, so a wave's result is
    independent of the order its expansions are screened in — the
    property that makes an S-shard walk (each shard screening its own
    subset of the wave, windows merged between waves) bit-identical to
    the single-host walk.  Default ``tighten=True`` keeps the PR-4
    single-host semantics (tighter screens, fewer bytes).

Soundness is inherited: stage 1 prunes only candidates whose lower bound
already fails the DADE test at threshold r² (the EF-th best so far, or the
seeded floor), so the ``passed`` set equals the fp32 screen's; fetch
elision is result-invariant (a skipped slab had no valid active rows).
Results are bit-identical to ``ref.graph_scan_ref``, the pure-jnp oracle
that replays the grid with the same tile helpers and models the same DMA
decisions — the parity the tests assert elementwise, fetch counters
included.

Shape/alignment contract (checked by ``repro.kernels.ops.graph_scan_kernel``):
``Q % block_q == 0``; ``adj_*`` rows a multiple of ``block_c`` with one
neighbour block per tile; ``D_pad % block_d == 0``; compiled (non-interpret)
lowering additionally needs ``block_q >= ops.min_block_q(int8) == 32``,
``block_c >= 32`` (int8 sublane floor — the adjacency build pads neighbour
blocks up to it) and ``block_d % 128 == 0`` (lane-aligned stage-2 slab DMA).

Scratch layout (identical to ``ivf_scan`` plus the seeded window and the
visited bitmap):

    codes_buf (2, BC, D) int8  — stage-1 double buffer (slots alternate)
    rows_buf  (BC, D) fp       — stage-2 landing buffer, filled slab-wise
    slot_s    (1, 2) i32 SMEM  — [0]: codes_buf slot holding this step's
                                 tile; [1]: offset of the last tile whose
                                 DMA was issued (-1 before the first) — the
                                 cross-gap reuse cursor: a real step whose
                                 offset matches it re-screens the landed
                                 buffer even if -1 gap steps intervened
    vis_s     (1, W) i32 VMEM  — packed visited bitmap for this query tile
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
# Same column semantics as the IVF megakernel — one ledger, two kernels.
from repro.kernels.ivf_scan import STATS_COLS  # noqa: F401  (re-export)
from repro.kernels.tiles import (
    dup_mask, merge_topk_tile, stage1_tile, stage2_need, stage2_slab,
)

__all__ = ["graph_scan_kernel_call", "STATS_COLS"]


def _kernel(
    # scalar prefetch
    offs_ref,  # (q_tiles, steps) i32 — candidate-tile offset per grid step;
    # steps past this wave's frontier carry -1 (skipped entirely)
    base_ref,  # (1,) i32 — global node id of local tile 0 (shard base);
    # 0 when the slab is the whole corpus
    # inputs
    qcodes_ref,  # (QT, D) int8 query codes
    q_ref,  # (QT, D) f32 exact rotated queries
    qscales_ref,  # (QT, S) f32 per-query block scales
    top0_sq_ref,  # (QT, EF) f32 — beam window carried in from the last wave
    top0_ids_ref,  # (QT, EF) i32
    rsq0_ref,  # (QT, 1) f32 thresholds carried in (min of seed and EF-th)
    vis0_ref,  # (1, W) i32 — this tile's packed visited bitmap carried in
    codes_hbm,  # (N_adj, D) int8 adjacency-flat codes — HBM-resident (ANY)
    rows_hbm,  # (N_adj, D) fp adjacency-flat rows — HBM-resident (ANY)
    ids_ref,  # (1, BC) i32 neighbour ids of this step's tile, -1 padding
    # (a (tiles, 1, BC) view of adj_ids, tile axis squeezed by the block)
    bscales_ref,  # (1, S) f32 corpus block scales
    eps_ref,  # (1, S) f32
    scale_ref,  # (1, S) f32
    # outputs
    top_sq_ref,  # (QT, EF) f32
    top_ids_ref,  # (QT, EF) i32
    stats_ref,  # (QT, 6) f32 — see STATS_COLS
    vis_ref,  # (1, W) i32 — bitmap with this wave's expansions marked
    # scratch
    top_sq_s,  # (QT, EF) f32 VMEM
    top_ids_s,  # (QT, EF) i32 VMEM
    rsq_s,  # (QT, 1) f32 VMEM
    stats_s,  # (QT, 6) f32 VMEM
    vis_s,  # (1, W) i32 VMEM — visited bitmap carried across the wave
    codes_buf,  # (2, BC, D) int8 VMEM — stage-1 double buffer
    rows_buf,  # (BC, D) fp VMEM — stage-2 landing buffer
    slot_s,  # (1, 2) i32 SMEM — [slot cursor, last issued offset]
    sem8,  # DMA (2,) — stage-1 per-slot semaphores
    sem32,  # DMA () — stage-2 slab semaphore
    *,
    num_steps: int,
    ef: int,
    thresh_col: int,
    block_c: int,
    block_d: int,
    slack: float,
    tighten: bool,
):
    i = pl.program_id(0)
    step = pl.program_id(1)

    def off_at(s):
        return offs_ref[i, s]

    def codes_dma(slot, s):
        return pltpu.make_async_copy(
            codes_hbm.at[pl.ds(off_at(s) * block_c, block_c), :],
            codes_buf.at[slot],
            sem8.at[slot],
        )

    off = off_at(step)
    real = off >= 0  # -1 steps (past this wave's frontier) ship nothing

    @pl.when(step == 0)
    def _init():
        # Resume the beam: the window, threshold, and visited bitmap carried
        # in from the previous wave (or the entry-point seed at wave 0) land
        # in scratch.
        top_sq_s[...] = top0_sq_ref[...]
        top_ids_s[...] = top0_ids_ref[...]
        rsq_s[...] = rsq0_ref[...]
        vis_s[...] = vis0_ref[...]
        stats_s[...] = jnp.zeros_like(stats_s)
        slot_s[0, 0] = 0
        slot_s[0, 1] = -1  # no tile issued yet

    @pl.when((step == 0) & real)
    def _warmup():
        codes_dma(0, step).start()  # wave 0's tile into slot 0

    cur = slot_s[0, 0]
    # Cross-gap buffer reuse: a real step whose offset equals the last
    # *issued* offset (not merely the previous step's — gap steps carry -1
    # and issue nothing) re-screens the landed buffer.  The reuse cursor
    # lives in SMEM and the oracle mirrors the same rule, so the fetch
    # counters stay bit-comparable.
    last = slot_s[0, 1]
    fresh = real & (off != last)
    # The tile resident (or inbound) in ``cur`` after this step: unchanged
    # by gap steps, this step's offset otherwise.
    resident = jnp.where(real, off, last)

    # Issue the NEXT real tile's int8 copy into the other slot before
    # waiting on the current one — stage-1 DMA overlaps this step's
    # screen work, exactly the ivf_scan pipeline.  The prefetch predicate
    # compares against ``resident`` (the reuse cursor's next value), so a
    # window ending in gap steps does not force a refetch of a tile that
    # is still landed.
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

    @pl.when(real)
    def _mark_expanded():
        # Set bit (off + base) of the packed per-tile bitmap: the expansion
        # commit the host driver used to perform.  base shifts local slab
        # offsets into the global node id space under sharded serving.
        goff = off + base_ref[0]
        word = goff // 32
        bit = jax.lax.rem(goff, 32)
        iota_w = jax.lax.broadcasted_iota(jnp.int32, vis_s.shape, 1)
        vis_s[...] = vis_s[...] | jnp.where(
            iota_w == word, jnp.left_shift(jnp.int32(1), bit), jnp.int32(0))

    @pl.when(real)
    def _screen_tile():
        ids = ids_ref[...]  # (1, BC)
        valid = ids >= 0
        validf = valid.astype(jnp.float32)
        rsq = rsq_s[...]  # frozen for this expansion (wave semantics)
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
            # Demand-paged fp32 slabs, identical to ivf_scan: slab s ships
            # only while a valid candidate is still active.
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

            dup = dup_mask(ids, top_ids_s[...], k=ef)
            new_sq = jnp.where(ok & ~dup, exact_sq, jnp.inf)
            top_sq, top_ids = merge_topk_tile(
                top_sq_s[...], top_ids_s[...], new_sq, ids, k=ef
            )
            top_sq_s[...] = top_sq
            top_ids_s[...] = top_ids
            # r² = the (thresh_col+1)-th best of the window — the K-th for
            # the paper's HNSW++-style decoupled threshold (default), the
            # EF-th for the coupled variant; tightens across the wave's
            # expansions on device, no host round-trip.  Sharded mode
            # (tighten=False) freezes the wave-start threshold instead:
            # tightening then happens only at the cross-shard merge, so the
            # wave is order-independent and shard-count-invariant.
            if tighten:
                rsq_s[...] = jnp.minimum(
                    rsq_s[...], top_sq[:, thresh_col:thresh_col + 1])

    @pl.when(step == num_steps - 1)
    def _finalize():
        top_sq_ref[...] = top_sq_s[...]
        top_ids_ref[...] = top_ids_s[...]
        stats_ref[...] = stats_s[...]
        vis_ref[...] = vis_s[...]


@functools.partial(
    jax.jit,
    static_argnames=("ef", "thresh_col", "block_q", "block_c", "block_d",
                     "slack", "tighten", "interpret"),
)
def graph_scan_kernel_call(
    step_offs: jax.Array,  # (q_tiles, steps) i32 per-step tile offsets
    qcodes: jax.Array,  # (Q, D) int8
    q_rot: jax.Array,  # (Q, D) f32
    qscales: jax.Array,  # (Q, S) f32
    top0_sq: jax.Array,  # (Q, EF) f32 beam window carried across waves
    top0_ids: jax.Array,  # (Q, EF) i32
    r0_sq: jax.Array,  # (Q,) f32 thresholds carried across waves
    vis0: jax.Array,  # (q_tiles, W) i32 packed visited bitmap carried in
    adj_codes: jax.Array,  # (N_adj, D) int8 adjacency-flat
    adj_rot: jax.Array,  # (N_adj, D) f32/bf16 adjacency-flat
    adj_ids: jax.Array,  # (N_adj,) i32, -1 per-block padding
    bscales: jax.Array,  # (S,) f32
    eps: jax.Array,  # (S,) f32 blocked table
    scale: jax.Array,  # (S,) f32
    vis_base: jax.Array | int = 0,  # () i32 global node id of local tile 0
    *,
    ef: int,
    thresh_col: int | None = None,
    block_q: int = 32,
    block_c: int = 32,
    block_d: int = 128,
    slack: float = 1e-4,
    tighten: bool = True,
    interpret: bool = False,
):
    """Launch one beam-scan wave.  Shapes must be pre-padded/aligned:
    ``Q % block_q == 0``, ``N_adj % block_c == 0``, ``D % block_d == 0``,
    every offset in ``step_offs`` -1 (skipped step) or < ``N_adj//block_c``
    (the wrapper ``repro.kernels.ops.graph_scan_kernel`` enforces this and
    owns padding/quantization).  ``adj_codes``/``adj_rot`` are passed
    UNBLOCKED — they stay HBM-resident and the kernel pages expansion tiles
    in manually.  ``vis0`` is the per-query-tile packed visited bitmap (bit
    ``vis_base + off`` marks local tile ``off`` expanded); the wrapper owns
    its sizing (words padded to the lane grid).

    Returns (top_sq (Q, EF) f32 ascending, top_ids (Q, EF) i32,
    stats (Q, 6) f32 — see ``STATS_COLS``, vis (q_tiles, W) i32); feed
    top/r²/vis back in as the next wave's carried state to continue the
    beam.  ``tighten=False`` freezes the screen threshold at ``r0_sq`` for
    the whole launch (sharded wave semantics — see the module docstring).
    """
    qn, dim = q_rot.shape
    if thresh_col is None:
        thresh_col = ef - 1
    if not 0 <= thresh_col < ef:
        raise ValueError(f"thresh_col must be in [0, ef), got {thresh_col}")
    n_adj = adj_rot.shape[0]
    s_count = dim // block_d
    if qn % block_q or n_adj % block_c or dim % block_d:
        raise ValueError(
            f"shapes must be padded: Q={qn}%{block_q}, N={n_adj}%{block_c}, "
            f"D={dim}%{block_d}"
        )
    if adj_codes.dtype != jnp.int8 or qcodes.dtype != jnp.int8:
        raise ValueError("codes must be int8")
    if not interpret and block_d % 128:
        raise ValueError(
            f"compiled lowering needs block_d % 128 == 0 (the demand-paged "
            f"stage-2 slab DMA must land on lane-aligned VMEM windows), got "
            f"block_d={block_d}")
    if eps.shape[0] != s_count or bscales.shape[0] != s_count:
        raise ValueError(f"table/scales must have {s_count} block steps")
    if not 1 <= ef <= 128:
        raise ValueError(f"ef must be in [1, 128], got {ef}")
    if top0_sq.shape != (qn, ef) or top0_ids.shape != (qn, ef):
        raise ValueError(
            f"beam window is {top0_sq.shape}/{top0_ids.shape}, need "
            f"({qn}, {ef})")
    q_tiles = qn // block_q
    num_steps = step_offs.shape[1]
    if step_offs.shape != (q_tiles, num_steps):
        raise ValueError(
            f"step_offs is {step_offs.shape}, need ({q_tiles}, steps)")
    vis_words = vis0.shape[1]
    if vis0.shape != (q_tiles, vis_words) or vis0.dtype != jnp.int32:
        raise ValueError(
            f"visited bitmap is {vis0.shape} {vis0.dtype}, need "
            f"({q_tiles}, words) int32")
    if not interpret and vis_words % 128:
        raise ValueError(
            f"compiled lowering needs the visited bitmap word count to be a "
            f"multiple of 128 (lane-aligned i32 blocks), got {vis_words}; "
            f"size it with repro.kernels.ops.graph_vis_words")

    grid = (q_tiles, num_steps)
    kernel = functools.partial(
        _kernel, num_steps=num_steps, ef=ef, thresh_col=thresh_col,
        block_c=block_c, block_d=block_d, slack=slack, tighten=tighten,
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, dim), lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((block_q, dim), lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((block_q, s_count), lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((block_q, ef), lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((block_q, ef), lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, s, offs, base: (i, 0)),
            # Per-tile operands carry a leading tile axis that the block
            # squeezes, so the block's last two dims equal the array's:
            # Mosaic refuses a (1, W) block of a (q_tiles, W) array once
            # q_tiles > 1, and a (1, block_c) id block unless block_c is a
            # multiple of 128.
            pl.BlockSpec((pl.squeezed, 1, vis_words),
                         lambda i, s, offs, base: (i, 0, 0)),
            # The adjacency streams are NOT pipelined by BlockSpec: the
            # kernel pages them manually (int8 double-buffered, fp32 slabs
            # on demand), so a fully-pruned expansion ships no fp32 bytes.
            pl.BlockSpec(memory_space=ANY_MEMSPACE),
            pl.BlockSpec(memory_space=ANY_MEMSPACE),
            # ids ride the automatic pipeline (4 B/row); -1 steps clamp to
            # tile 0, which the kernel never reads (gap steps are fully
            # predicated out via ``real``).
            pl.BlockSpec((pl.squeezed, 1, block_c),
                         lambda i, s, offs, base:
                         (jnp.maximum(offs[i, s], 0), 0, 0)),
            pl.BlockSpec((1, s_count), lambda i, s, offs, base: (0, 0)),
            pl.BlockSpec((1, s_count), lambda i, s, offs, base: (0, 0)),
            pl.BlockSpec((1, s_count), lambda i, s, offs, base: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_q, ef), lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((block_q, ef), lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((block_q, len(STATS_COLS)),
                         lambda i, s, offs, base: (i, 0)),
            pl.BlockSpec((pl.squeezed, 1, vis_words),
                         lambda i, s, offs, base: (i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, ef), jnp.float32),
            pltpu.VMEM((block_q, ef), jnp.int32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, len(STATS_COLS)), jnp.float32),
            pltpu.VMEM((1, vis_words), jnp.int32),
            pltpu.VMEM((2, block_c, dim), jnp.int8),
            pltpu.VMEM((block_c, dim), adj_rot.dtype),
            pltpu.SMEM((1, 2), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out_shapes = (
        jax.ShapeDtypeStruct((qn, ef), jnp.float32),
        jax.ShapeDtypeStruct((qn, ef), jnp.int32),
        jax.ShapeDtypeStruct((qn, len(STATS_COLS)), jnp.float32),
        jax.ShapeDtypeStruct((q_tiles, 1, vis_words), jnp.int32),
    )
    top_sq, top_ids, stats, vis = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        step_offs.astype(jnp.int32),
        jnp.asarray(vis_base, jnp.int32).reshape(1),
        qcodes,
        q_rot.astype(jnp.float32),
        qscales.astype(jnp.float32),
        top0_sq.astype(jnp.float32),
        top0_ids.astype(jnp.int32),
        r0_sq.reshape(-1, 1).astype(jnp.float32),
        vis0.astype(jnp.int32).reshape(q_tiles, 1, vis_words),
        adj_codes,
        adj_rot,  # f32 or bf16 — stage 2 upcasts per block
        adj_ids.astype(jnp.int32).reshape(n_adj // block_c, 1, block_c),
        bscales.reshape(1, -1).astype(jnp.float32),
        eps.reshape(1, -1).astype(jnp.float32),
        scale.reshape(1, -1).astype(jnp.float32),
    )
    return top_sq, top_ids, stats, vis.reshape(q_tiles, vis_words)
