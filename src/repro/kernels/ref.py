"""Pure-jnp oracles for the Pallas kernels (bit-level semantics match).

``dade_dco_ref`` mirrors ``dade_dco.dade_dco_kernel_call`` exactly: same
block-checkpoint schedule (d = (s+1)·DB), same MXU decomposition
(qn + cn - 2q·oᵀ with a max(·, 0) clamp), same retire/passed rules — so
tests can assert elementwise equality, not just statistical agreement.
``quant_dco_ref`` does the same for the int8 lower-bound prefilter kernel
(``quant_dco.quant_dco_kernel_call``): dequantize-then-decompose, identical
lower-bound formula and retire rules.  ``ivf_scan_ref`` replays the fused
IVF wave-scan megakernel (``ivf_scan.ivf_scan_kernel_call``) grid step by
grid step *with the kernel's own tile helpers* (``repro.kernels.tiles``),
so parity is structural; it also models the demand-paged memory behaviour —
the stage-1 same-offset DMA elision and the stage-2 fetch that only happens
when the stage-1 survivor count is nonzero — so the fetch counters in
``stats`` are asserted tile-by-tile, not just the screen results.  Its
optional trace exposes the per-wave frozen thresholds, pass masks, and
fetch decisions the megakernel keeps in VMEM scratch, which the tests
replay against ``dco_screen_batch``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.tiles import dade_threshold, lb_penalized

__all__ = ["dade_dco_ref", "quant_dco_ref", "ivf_scan_ref", "graph_scan_ref"]


@partial(jax.jit, static_argnames=("block_d",))
def dade_dco_ref(
    q_rot: jax.Array,  # (Q, D)
    cands_rot: jax.Array,  # (N, D)
    eps: jax.Array,  # (S,)
    scale: jax.Array,  # (S,)
    r_sq: jax.Array,  # (Q,)
    *,
    block_d: int = 128,
):
    qn, dim = q_rot.shape
    n = cands_rot.shape[0]
    s_count = dim // block_d
    assert s_count * block_d == dim and eps.shape[0] == s_count

    q = q_rot.astype(jnp.float32).reshape(qn, s_count, block_d)
    c = cands_rot.astype(jnp.float32).reshape(n, s_count, block_d)
    dot = jnp.einsum("qsd,csd->sqc", q, c, preferred_element_type=jnp.float32)
    qnorm = jnp.sum(q * q, axis=2).T[:, :, None]  # (S, Q, 1)
    cnorm = jnp.sum(c * c, axis=2).T[:, None, :]  # (S, 1, C)
    block_sq = jnp.maximum(qnorm + cnorm - 2.0 * dot, 0.0)  # (S, Q, C)
    psum = jnp.cumsum(block_sq, axis=0)  # (S, Q, C)

    est_all = psum * scale[:, None, None]
    thresh = dade_threshold(eps[:, None, None], r_sq[None, :, None])
    reject = est_all > thresh
    # Last block never "rejects" — survivors retire exact there.
    reject = reject.at[-1].set(False)

    s_idx = jnp.arange(s_count)
    first_reject = jnp.min(
        jnp.where(reject, s_idx[:, None, None], s_count), axis=0
    )  # (Q, C)
    never = first_reject == s_count
    retire_s = jnp.where(never, s_count - 1, first_reject)

    est_sq = jnp.take_along_axis(
        jnp.moveaxis(est_all, 0, -1), retire_s[..., None], axis=-1
    )[..., 0]
    dims_used = ((retire_s + 1) * block_d).astype(jnp.int32)
    passed = jnp.logical_and(never, est_sq <= r_sq[:, None])
    return est_sq, passed.astype(jnp.int32), dims_used


@partial(jax.jit, static_argnames=("block_d", "slack"))
def quant_dco_ref(
    q_rot: jax.Array,  # (Q, D) f32
    codes: jax.Array,  # (N, D) int8
    scales: jax.Array,  # (D,) f32
    eps: jax.Array,  # (S,)
    scale: jax.Array,  # (S,)
    ecum: jax.Array,  # (S,) E(d) at block checkpoints
    r_sq: jax.Array,  # (Q,)
    *,
    block_d: int = 128,
    slack: float = 1e-4,
):
    """Oracle for the int8 lower-bound prefilter kernel."""
    qn, dim = q_rot.shape
    n = codes.shape[0]
    s_count = dim // block_d
    assert s_count * block_d == dim and eps.shape[0] == s_count

    q = q_rot.astype(jnp.float32).reshape(qn, s_count, block_d)
    cf = (codes.astype(jnp.float32) * scales.astype(jnp.float32)[None, :]).reshape(
        n, s_count, block_d
    )
    dot = jnp.einsum("qsd,csd->sqc", q, cf, preferred_element_type=jnp.float32)
    qnorm = jnp.sum(q * q, axis=2).T[:, :, None]  # (S, Q, 1)
    cnorm = jnp.sum(cf * cf, axis=2).T[:, None, :]  # (S, 1, C)
    block_sq = jnp.maximum(qnorm + cnorm - 2.0 * dot, 0.0)
    psum = jnp.cumsum(block_sq, axis=0)  # (S, Q, C)

    est_all = lb_penalized(
        psum, ecum[:, None, None], scale[:, None, None], slack=slack)
    thresh = dade_threshold(eps[:, None, None], r_sq[None, :, None])
    # Rejecting is sound at every checkpoint, the last included.
    reject = est_all > thresh

    s_idx = jnp.arange(s_count)
    first_reject = jnp.min(
        jnp.where(reject, s_idx[:, None, None], s_count), axis=0
    )  # (Q, C)
    pruned = first_reject < s_count
    retire_s = jnp.where(pruned, first_reject, s_count - 1)

    lb_sq = jnp.take_along_axis(
        jnp.moveaxis(est_all, 0, -1), retire_s[..., None], axis=-1
    )[..., 0]
    lb_dims = ((retire_s + 1) * block_d).astype(jnp.int32)
    return lb_sq, pruned.astype(jnp.int32), lb_dims


def ivf_scan_ref(
    tile_offs: jax.Array,  # (q_tiles, P, cap_tiles) i32 per-step offsets
    qcodes: jax.Array,  # (Q, D) int8
    q_rot: jax.Array,  # (Q, D) f32
    qscales: jax.Array,  # (Q, S) f32
    r0_sq: jax.Array,  # (Q,) f32
    top0_sq: jax.Array,  # (Q, K) f32 seeded top-K window (inf = empty)
    top0_ids: jax.Array,  # (Q, K) i32 seeded top-K ids (-1 = empty)
    flat_codes: jax.Array,  # (N_pad, D) int8
    flat_rot: jax.Array,  # (N_pad, D) f32
    flat_ids: jax.Array,  # (N_pad,) i32
    bscales: jax.Array,  # (S,) f32
    eps: jax.Array,  # (S,) f32
    scale: jax.Array,  # (S,) f32
    *,
    k: int,
    block_q: int,
    block_c: int,
    block_d: int,
    cap_tiles: int,
    slack: float = 1e-4,
    return_trace: bool = False,
):
    """Oracle for the demand-paged fused IVF wave-scan megakernel.

    Pure-jnp replay of the (q_tiles, P, cap_tiles) grid using the kernel's
    own ``repro.kernels.tiles`` helpers and the same scratch-carry semantics
    (threshold frozen per tile, tightened after the merge).  The memory
    behaviour of the manual pipeline is modelled exactly:

      * steps with offset -1 (out-of-span window tail) are skipped — no
        DMA, no screen, no stats;
      * a real step whose offset equals the last *issued* offset re-uses
        the landed int8 buffer even when -1 gap steps intervened — the
        kernel's SMEM reuse cursor (``s1_tiles_fetched`` counts only fresh
        offsets); and
      * fp32 slabs are "fetched" per ``tiles.stage2_need`` — the first iff
        the stage-1 survivor count is nonzero, later ones only while a
        valid candidate is still active (``s2_slabs_fetched``) — the
        elision the demand-paged kernel performs in hardware.

    With ``return_trace`` additionally returns a list of per-(tile, probe,
    ctile) records for the real steps, exposing the frozen r², the scanned
    window, the stage-1/stage-2 masks, and the fetch decisions (``alive``,
    ``fetched``, ``fresh``, ``slabs``) — the state the kernel keeps in
    VMEM/SMEM — so tests can replay each wave against ``dco_screen_batch``
    and assert that no tile with survivors is ever elided.
    """
    from repro.kernels.ivf_scan import STATS_COLS
    from repro.kernels.tiles import merge_topk_tile, stage1_tile, stage2_tile

    qn, dim = q_rot.shape
    q_tiles = qn // block_q
    num_probes = tile_offs.shape[1]
    top_sq = []
    top_ids = []
    stats = []
    trace = []
    for i in range(q_tiles):
        qs = slice(i * block_q, (i + 1) * block_q)
        t_sq = jnp.asarray(top0_sq[qs], jnp.float32)
        t_ids = jnp.asarray(top0_ids[qs], jnp.int32)
        rsq = r0_sq[qs].reshape(-1, 1).astype(jnp.float32)
        st = jnp.zeros((block_q, len(STATS_COLS)), jnp.float32)
        last_off = None  # last issued offset — the kernel's reuse cursor
        for p in range(num_probes):
            for t in range(cap_tiles):
                off = int(tile_offs[i, p, t])
                if off < 0:
                    continue  # skipped step: the kernel ships nothing
                fresh = off != last_off
                last_off = off
                rows = slice(off * block_c, (off + 1) * block_c)
                ids = flat_ids[rows].reshape(1, -1)
                valid = ids >= 0
                validf = valid.astype(jnp.float32)
                rsq_frozen = rsq
                active8, d8 = stage1_tile(
                    qcodes[qs], qscales[qs], flat_codes[rows], bscales,
                    eps, scale, rsq_frozen, block_d=block_d, slack=slack,
                )
                d8_sum = jnp.sum(d8 * validf, axis=1, keepdims=True)
                nvalid = jnp.broadcast_to(
                    jnp.sum(validf, axis=1, keepdims=True), d8_sum.shape)
                zero = jnp.zeros_like(d8_sum)
                one = jnp.ones_like(d8_sum)
                s1f = one if fresh else zero
                st = st + jnp.concatenate(
                    [d8_sum, zero, nvalid, zero, zero, s1f, zero], axis=1)
                alive = int(jnp.sum((active8 & valid).astype(jnp.int32)))
                rec = dict(tile=i, probe=p, ctile=t, row_start=off * block_c,
                           ids=ids[0], rsq=rsq_frozen[:, 0], active8=active8,
                           valid=valid[0], alive=alive, fetched=alive > 0,
                           fresh=fresh, slabs=0.0)
                if alive > 0:
                    # The demand-paged kernel ships fp32 slabs only here,
                    # and only while stage2_need keeps asking for them.
                    exact_sq, passed, d32, slabs = stage2_tile(
                        q_rot[qs], flat_rot[rows], eps, scale, rsq_frozen,
                        active8, valid, block_d=block_d,
                    )
                    ok = passed & valid
                    d32_sum = jnp.sum(d32 * validf, axis=1, keepdims=True)
                    npass = jnp.sum(ok.astype(jnp.float32), axis=1, keepdims=True)
                    t_sq, t_ids, rounds = merge_topk_tile(
                        t_sq, t_ids, exact_sq, ok, ids)
                    z = jnp.zeros_like(d32_sum)
                    slabs_col = jnp.broadcast_to(slabs, d32_sum.shape)
                    rounds_col = jnp.broadcast_to(
                        rounds.astype(jnp.float32), d32_sum.shape)
                    st = st + jnp.concatenate(
                        [z, d32_sum, z, npass, slabs_col, z, rounds_col],
                        axis=1)
                    rsq = jnp.minimum(rsq, t_sq[:, k - 1:k])
                    rec.update(passed=passed, exact_sq=exact_sq,
                               slabs=float(slabs))
                else:
                    rec.update(passed=jnp.zeros_like(active8), exact_sq=None)
                if return_trace:
                    trace.append(rec)
        top_sq.append(t_sq)
        top_ids.append(t_ids)
        stats.append(st)
    out = (jnp.concatenate(top_sq, 0), jnp.concatenate(top_ids, 0),
           jnp.concatenate(stats, 0))
    if return_trace:
        return out + (trace,)
    return out


@partial(jax.jit, static_argnames=("block_d", "slack"))
def _graph_step_stage1(qcodes, qscales, codes, bscales, eps, scale, rsq, ids,
                       *, block_d: int, slack: float):
    """Stage 1 of one expansion step of ``graph_scan_ref``.  The step's ops
    are compiled together so the host replay costs a launch per step, not
    one per op (hundreds per step on an accelerator); the arithmetic is the
    ``tiles`` helpers', unchanged.  Returns (active8, d8_sum, nvalid,
    alive)."""
    from repro.kernels.tiles import stage1_tile

    valid = ids >= 0
    validf = valid.astype(jnp.float32)
    active8, d8 = stage1_tile(qcodes, qscales, codes, bscales, eps, scale,
                              rsq, block_d=block_d, slack=slack)
    d8_sum = jnp.sum(d8 * validf, axis=1, keepdims=True)
    nvalid = jnp.broadcast_to(
        jnp.sum(validf, axis=1, keepdims=True), d8_sum.shape)
    alive = jnp.sum((active8 & valid).astype(jnp.int32))
    return active8, d8_sum, nvalid, alive


@partial(jax.jit, static_argnames=("block_d", "thresh_col", "tighten"))
def _graph_step_stage2(q, rows, eps, scale, rsq, active8, ids, t_sq, t_ids,
                       *, block_d: int, thresh_col: int, tighten: bool):
    """Stage 2 and the window merge of one expansion step of
    ``graph_scan_ref`` (compiled per step, as ``_graph_step_stage1``).
    Returns (t_sq, t_ids, rsq, d32_sum, npass, slabs, rounds, passed,
    exact_sq)."""
    from repro.kernels.tiles import merge_topk_tile, stage2_tile

    valid = ids >= 0
    exact_sq, passed, d32, slabs = stage2_tile(
        q, rows, eps, scale, rsq, active8, valid, block_d=block_d)
    ok = passed & valid
    d32_sum = jnp.sum(d32 * valid.astype(jnp.float32), axis=1, keepdims=True)
    npass = jnp.sum(ok.astype(jnp.float32), axis=1, keepdims=True)
    t_sq, t_ids, rounds = merge_topk_tile(t_sq, t_ids, exact_sq, ok, ids)
    if tighten:
        rsq = jnp.minimum(rsq, t_sq[:, thresh_col:thresh_col + 1])
    return t_sq, t_ids, rsq, d32_sum, npass, slabs, rounds, passed, exact_sq


def graph_scan_ref(
    step_offs: jax.Array,  # (q_tiles, steps) i32 per-step tile offsets
    qcodes: jax.Array,  # (Q, D) int8
    q_rot: jax.Array,  # (Q, D) f32
    qscales: jax.Array,  # (Q, S) f32
    top0_sq: jax.Array,  # (Q, EF) f32 beam window carried across waves
    top0_ids: jax.Array,  # (Q, EF) i32
    r0_sq: jax.Array,  # (Q,) f32
    vis0: jax.Array,  # (q_tiles, W) i32 packed visited bitmap carried in
    adj_codes: jax.Array,  # (N_adj, D) int8 adjacency-flat
    adj_rot: jax.Array,  # (N_adj, D) f32
    adj_ids: jax.Array,  # (N_adj,) i32
    bscales: jax.Array,  # (S,) f32
    eps: jax.Array,  # (S,) f32
    scale: jax.Array,  # (S,) f32
    vis_base: int = 0,
    *,
    ef: int,
    thresh_col: int | None = None,
    block_q: int,
    block_c: int,
    block_d: int,
    slack: float = 1e-4,
    tighten: bool = True,
    return_trace: bool = False,
):
    """Oracle for the fused graph beam-scan megakernel (one wave).

    Pure-jnp replay of the (q_tiles, steps) grid using the kernel's own
    ``repro.kernels.tiles`` helpers and the same scratch-carry semantics:
    the beam window / threshold / visited bitmap are SEEDED from
    ``top0``/``r0_sq``/``vis0`` (the state the previous wave's launch
    returned), frozen per expansion, and — unless ``tighten=False``, the
    sharded frozen-wave mode — the threshold is tightened after each merge.
    The manual pipeline's memory behaviour is modelled exactly as in
    ``ivf_scan_ref``: -1 steps ship nothing, a step repeating the last
    *issued* offset (even across -1 gap steps — the SMEM reuse cursor)
    reuses the landed buffer (``s1_tiles_fetched`` counts fresh offsets
    only), and fp32 slabs are fetched per ``tiles.stage2_need``.

    Mask ownership mirrors the kernel: every real step sets bit
    ``vis_base + off`` of its query tile's packed bitmap (the expansion
    commit the host driver used to own), and the final bitmap is returned
    as the fourth output.

    With ``return_trace`` additionally returns per-(tile, step) records for
    the real steps exposing the frozen r², the scanned neighbour block, the
    stage-1/stage-2 masks, the fetch decisions (``alive``, ``fetched``,
    ``fresh``, ``slabs``), and the marked global node (``marked``) — so
    tests can replay each expansion against ``dco_screen_batch`` and assert
    fetch soundness and mask ownership per wave.
    """
    import numpy as np

    from repro.kernels.ivf_scan import STATS_COLS

    qn, dim = q_rot.shape
    if thresh_col is None:
        thresh_col = ef - 1
    q_tiles = qn // block_q
    num_steps = step_offs.shape[1]
    vis = np.array(vis0, dtype=np.int32, copy=True)
    top_sq = []
    top_ids = []
    stats = []
    trace = []
    for i in range(q_tiles):
        qs = slice(i * block_q, (i + 1) * block_q)
        t_sq = jnp.asarray(top0_sq[qs], jnp.float32)
        t_ids = jnp.asarray(top0_ids[qs], jnp.int32)
        rsq = r0_sq[qs].reshape(-1, 1).astype(jnp.float32)
        st = jnp.zeros((block_q, len(STATS_COLS)), jnp.float32)
        tile_qcodes, tile_qscales, tile_q = qcodes[qs], qscales[qs], q_rot[qs]
        last_off = None  # last issued offset — the kernel's reuse cursor
        for s in range(num_steps):
            off = int(step_offs[i, s])
            if off < 0:
                continue  # skipped step: the kernel ships nothing
            fresh = off != last_off
            last_off = off
            goff = off + int(vis_base)
            vis[i, goff // 32] |= np.int32(1) << np.int32(goff % 32)
            rows = slice(off * block_c, (off + 1) * block_c)
            ids = adj_ids[rows].reshape(1, -1)
            rsq_frozen = rsq
            active8, d8_sum, nvalid, alive = _graph_step_stage1(
                tile_qcodes, tile_qscales, adj_codes[rows], bscales, eps,
                scale, rsq_frozen, ids, block_d=block_d, slack=slack)
            zero = jnp.zeros_like(d8_sum)
            one = jnp.ones_like(d8_sum)
            s1f = one if fresh else zero
            st = st + jnp.concatenate(
                [d8_sum, zero, nvalid, zero, zero, s1f, zero], axis=1)
            alive = int(alive)
            rec = dict(tile=i, step=s, row_start=off * block_c,
                       ids=ids[0], rsq=rsq_frozen[:, 0], active8=active8,
                       valid=ids[0] >= 0, alive=alive, fetched=alive > 0,
                       fresh=fresh, slabs=0.0, marked=goff)
            if alive > 0:
                (t_sq, t_ids, rsq, d32_sum, npass, slabs, rounds, passed,
                 exact_sq) = _graph_step_stage2(
                    tile_q, adj_rot[rows], eps, scale, rsq_frozen, active8,
                    ids, t_sq, t_ids, block_d=block_d,
                    thresh_col=thresh_col, tighten=tighten)
                z = jnp.zeros_like(d32_sum)
                slabs_col = jnp.broadcast_to(slabs, d32_sum.shape)
                rounds_col = jnp.broadcast_to(
                    rounds.astype(jnp.float32), d32_sum.shape)
                st = st + jnp.concatenate(
                    [z, d32_sum, z, npass, slabs_col, z, rounds_col], axis=1)
                rec.update(passed=passed, exact_sq=exact_sq,
                           slabs=float(slabs))
            else:
                rec.update(passed=jnp.zeros_like(active8), exact_sq=None)
            if return_trace:
                trace.append(rec)
        top_sq.append(t_sq)
        top_ids.append(t_ids)
        stats.append(st)
    out = (jnp.concatenate(top_sq, 0), jnp.concatenate(top_ids, 0),
           jnp.concatenate(stats, 0), jnp.asarray(vis))
    if return_trace:
        return out + (trace,)
    return out
