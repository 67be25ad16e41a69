"""Per-tile DCO stage helpers shared by every kernel and its oracle.

One module owns the arithmetic that the correctness guarantees rest on, so
the int8 prefilter kernel (``quant_dco.py``), the fused IVF megakernel
(``ivf_scan.py``), the fused graph beam-scan megakernel
(``graph_scan.py``), the fp32 screen kernel (``dade_dco.py``) and the
pure-jnp oracles (``ref.py``) cannot drift apart (the stage-helper
contract table lives in ``docs/ARCHITECTURE.md`` §2):

  * ``mxu_block_sq`` — the MXU-friendly ``||q-o||² = qn + cn − 2 q·oᵀ``
    decomposition with the ``max(·, 0)`` clamp, f32 accumulation.
  * ``lb_penalized`` — the sound quantization lower bound
    ``max(0, √psum − E)² · (1 − slack) · scale`` (repro.quant.scalar).
  * ``dade_threshold`` — the hypothesis-test threshold ``(1+ε)²·r²``.
  * ``stage1_tile`` / ``stage2_tile`` — the fused kernel's two screening
    stages over one (BQ, BC) candidate tile.
  * ``merge_topk_tile`` / ``dup_mask`` — the on-device top-K maintenance.

Everything here is pure jnp (no pallas primitives), so the same functions
trace inside a Mosaic kernel body, in interpret mode, and in the eager
oracle replay — kernel-vs-oracle parity is structural, not statistical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "mxu_block_sq", "lb_penalized", "dade_threshold",
    "stage1_tile", "stage2_slab", "stage2_need", "stage2_tile",
    "merge_topk_tile", "dup_mask",
]


def mxu_block_sq(qb, cb):
    """(BQ, BC) clamped squared partial distance of one dim-block.

    ``qn + cn - 2 q·cᵀ`` with f32 accumulation on the MXU and the
    ``max(·, 0)`` clamp (the decomposition can go negative in f32 where the
    direct sum of squares cannot).  Both operands must already be f32.
    HIGHEST precision: this is the exact stage-2 distance, and TPU's
    default f32 dot (one bf16 pass) would make it approximate.
    """
    dot = jax.lax.dot_general(
        qb, cb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    qn = jnp.sum(qb * qb, axis=1, keepdims=True)
    cn = jnp.sum(cb * cb, axis=1, keepdims=True).T
    return jnp.maximum(qn + cn - 2.0 * dot, 0.0)


def lb_penalized(psum, eband, scale, *, slack: float):
    """Scaled sound lower bound of the exact partial distance.

    ``max(0, sqrt(psum) - eband)^2 * (1 - slack) * scale`` — broadcasts, so
    the kernels call it per block scalar and the oracles over (S, Q, C).
    Never exceeds the scaled exact partial distance (repro.quant.scalar), so
    rejecting against ``dade_threshold`` is sound at EVERY checkpoint.
    """
    root = jnp.maximum(jnp.sqrt(psum) - eband, 0.0)
    return root * root * (1.0 - slack) * scale


def dade_threshold(eps, rsq):
    """The DADE hypothesis-test rejection threshold ``(1+eps)^2 * r^2``."""
    return (1.0 + eps) ** 2 * rsq


def stage1_tile(qcodes, qscales, ccodes, bscales, eps, scale, rsq,
                *, block_d: int, slack: float):
    """int8×int8 lower-bound prefilter over one (BQ, BC) tile.

    Args:
      qcodes: (BQ, D) int8 query codes (per-query per-block scales).
      qscales: (BQ, S) f32 query block scales t.
      ccodes: (BC, D) int8 corpus codes (per-block scales).
      bscales: (S,) f32 corpus block scales s.
      eps, scale: (S,) blocked DADE table.
      rsq: (BQ, 1) f32 frozen thresholds for this tile.
    Returns (active (BQ, BC) bool stage-1 survivors, d8 (BQ, BC) f32 int8
    dims consumed per row — the retirement checkpoint, dade-style).
    """
    s_count = qcodes.shape[1] // block_d
    bq, bc = qcodes.shape[0], ccodes.shape[0]
    psum = jnp.zeros((bq, bc), jnp.float32)
    active = jnp.ones((bq, bc), bool)
    d8 = jnp.zeros((bq, bc), jnp.float32)
    ec2 = jnp.zeros((), jnp.float32)
    eq2 = jnp.zeros((bq, 1), jnp.float32)
    for s in range(s_count):
        sl = slice(s * block_d, (s + 1) * block_d)
        qc = qcodes[:, sl]
        cc = ccodes[:, sl]
        dot_i = jax.lax.dot_general(
            qc, cc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
        )  # (BQ, BC) int32 on the MXU
        t_q = qscales[:, s:s + 1]  # (BQ, 1)
        s_b = bscales[s]
        qn_i = jnp.sum(qc.astype(jnp.int32) ** 2, axis=1, keepdims=True)
        cn_i = jnp.sum(cc.astype(jnp.int32) ** 2, axis=1, keepdims=True).T
        qn = qn_i.astype(jnp.float32) * (t_q * t_q)
        cn = cn_i.astype(jnp.float32) * (s_b * s_b)
        dotf = dot_i.astype(jnp.float32) * (t_q * s_b)
        psum = psum + jnp.maximum(qn + cn - 2.0 * dotf, 0.0)
        # Cumulative error bands: corpus (scalar) + query (per row).
        ec2 = ec2 + block_d * (s_b * 0.5) ** 2
        eq2 = eq2 + block_d * (t_q * 0.5) ** 2
        eband = jnp.sqrt(ec2) + jnp.sqrt(eq2)  # (BQ, 1)
        d8 = d8 + jnp.where(active, float(block_d), 0.0)
        lb = lb_penalized(psum, eband, scale[s], slack=slack)
        thresh = dade_threshold(eps[s], rsq)
        # The lower bound never exceeds the exact partial distance, so
        # rejecting is sound at every checkpoint, the last included.
        active = active & ~(lb > thresh)
    return active, d8


def stage2_slab(psum, active, qb, cb, eps_s, scale_s, rsq,
                *, block_d: int, is_last: bool):
    """One dim-slab step of the blocked fp32 DADE re-screen.

    Shared by the demand-paged kernel's slab loop (which interleaves the
    fp32 slab DMAs with these steps) and ``stage2_tile`` below (the
    oracle's whole-tile replay), so the screen arithmetic cannot drift from
    the paging logic.  Same checkpoint/retire semantics as ``dade_dco.py``:
    per-block clamp, reject at non-terminal checkpoints, survivors retire
    exact.  Returns (psum, active, d32_increment).
    """
    psum = psum + mxu_block_sq(qb, cb)
    d32_inc = jnp.where(active, float(block_d), 0.0)
    est = psum * scale_s
    reject = active & (est > dade_threshold(eps_s, rsq)) & (not is_last)
    return psum, active & ~reject, d32_inc


def stage2_need(active, valid):
    """Demand-paging decision for a fp32 slab: fetch iff any *valid*
    candidate is still active.  Rows that are active but invalid (sentinel
    gap/tail) can never pass, so they must not force fp32 traffic; rows
    that stay active through slab s are guaranteed slab s was fetched, so
    every surviving distance is exact."""
    return jnp.sum((active & valid).astype(jnp.int32)) > 0


def stage2_tile(q, c, eps, scale, rsq, active0, valid, *, block_d: int):
    """Blocked fp32 DADE screen of the stage-1 survivors in one tile.

    Pure whole-tile replay of the kernel's demand-paged slab loop (same
    ``stage2_slab`` steps, same ``stage2_need`` decisions).  Rows with
    ``active0`` False (stage-1 pruned) consume no fp32 dims and never pass.
    Returns (exact_sq (BQ, BC), passed (BQ, BC) bool, d32 (BQ, BC) f32,
    slabs — the number of (BC, block_d) fp32 slabs a paging kernel ships
    for this tile).
    """
    s_count = q.shape[1] // block_d
    bq, bc = q.shape[0], c.shape[0]
    psum = jnp.zeros((bq, bc), jnp.float32)
    active = active0
    d32 = jnp.zeros((bq, bc), jnp.float32)
    slabs = jnp.zeros((), jnp.float32)
    for s in range(s_count):
        sl = slice(s * block_d, (s + 1) * block_d)
        slabs = slabs + jnp.where(stage2_need(active, valid), 1.0, 0.0)
        # Upcast per block: the serving corpus streams as bf16 (2 B/dim);
        # accumulation stays f32 either way.
        qb = q[:, sl].astype(jnp.float32)
        cb = c[:, sl].astype(jnp.float32)
        psum, active, d32_inc = stage2_slab(
            psum, active, qb, cb, eps[s], scale[s], rsq,
            block_d=block_d, is_last=s == s_count - 1)
        d32 = d32 + d32_inc
    passed = active & (psum <= rsq)
    return psum, passed, d32, slabs


def merge_topk_tile(top_sq, top_ids, new_sq, new_ids, *, k: int):
    """Merge a (BQ, BC) candidate tile into the running (BQ, K) top-K.

    Portable K-step selection (min + one-hot extract) instead of
    ``lax.top_k`` so the same code lowers in Mosaic and interpret mode.
    The loop unrolls K times, which bounds K at 128 (the megakernel
    wrappers enforce ``1 <= k/ef <= 128``).  ``new_sq`` must already be
    inf for rows that must not enter (invalid, failed, duplicate).
    Returns (top_sq, top_ids) sorted ascending.
    """
    all_sq = jnp.concatenate([top_sq, new_sq], axis=1)
    all_ids = jnp.concatenate([top_ids, jnp.broadcast_to(new_ids, new_sq.shape)], axis=1)
    iota = jax.lax.broadcasted_iota(jnp.int32, all_sq.shape, 1)
    sq_cols, id_cols = [], []
    for _ in range(k):
        m = jnp.min(all_sq, axis=1, keepdims=True)  # (BQ, 1)
        am = jnp.argmin(all_sq, axis=1).astype(jnp.int32)
        onehot = iota == am[:, None]
        sel = jnp.sum(jnp.where(onehot, all_ids, 0), axis=1, keepdims=True)
        sel = jnp.where(jnp.isinf(m), jnp.int32(-1), sel)
        sq_cols.append(m)
        id_cols.append(sel)
        all_sq = jnp.where(onehot, jnp.inf, all_sq)
    return jnp.concatenate(sq_cols, axis=1), jnp.concatenate(id_cols, axis=1)


def dup_mask(new_ids, top_ids, *, k: int):
    """(BQ, BC) bool — candidate id already present in the running top-K.

    Probed windows can overlap (offsets round down to tile boundaries and
    adjacent buckets share tiles), so the same corpus row may be scanned
    twice; without this mask it could occupy two top-K slots.  Checking
    against the *current* top-K suffices: r never loosens, so a row that
    fell out of the top-K can never re-enter.
    """
    dup = jnp.zeros(new_ids.shape, bool)
    for j in range(k):
        dup = dup | ((new_ids == top_ids[:, j:j + 1]) & (top_ids[:, j:j + 1] >= 0))
    return dup
