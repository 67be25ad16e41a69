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
  * ``merge_topk_tile`` — the on-device top-K maintenance: entrants are
    inserted one round at a time, duplicates checked on those rows alone.

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
    "merge_topk_tile",
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


def merge_topk_tile(top_sq, top_ids, exact_sq, ok, new_ids):
    """Insert a (BQ, BC) tile's entrants into the running (BQ, K) top-K.

    ``top_sq`` ascending (inf slots carry id -1); ``exact_sq`` the tile's
    distances, of which only ``ok`` rows may enter; ``new_ids`` (1, BC).
    Each round takes every query row's least remaining ``(sq, column)``
    pair — re-derived from the read-only tile as the least pair after the
    previous round's, so the loop carries only (BQ, K)/(BQ, 1) state — and
    inserts it after every window value ``<=`` it, if it beats the K-th and
    its id is not already in the window the tile started from (overlapping
    probe windows rescan rows; r never loosens, so a row that fell out can
    never come back).  Rounds run while some row's next pair beats its K-th:
    none when nothing can enter.  The result is the K least of
    ``[top, entrants]`` by a stable sort, old entries first among equal
    values.  Returns (top_sq, top_ids, rounds).
    """
    k = top_sq.shape[1]
    bc = exact_sq.shape[1]
    cand = jnp.where(ok, exact_sq, jnp.inf)
    col = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, top_sq.shape, 1)
    old_ids = jnp.where(top_ids >= 0, top_ids, -2)  # -1 ids match nothing

    def least(after):
        rest = jnp.where(after, cand, jnp.inf)
        m = jnp.min(rest, axis=1, keepdims=True)
        c = jnp.min(jnp.where(after & (rest == m), col, bc), axis=1,
                    keepdims=True)
        return m, c

    def live(state):
        t_sq, _, m, _, _ = state
        return jnp.sum((m < t_sq[:, k - 1:k]).astype(jnp.int32)) > 0

    def insert(state):
        t_sq, t_ids, m, c, rounds = state
        cid = jnp.sum(jnp.where(col == c, new_ids, 0), axis=1, keepdims=True)
        dup = jnp.sum((old_ids == cid).astype(jnp.int32), axis=1,
                      keepdims=True) > 0
        enter = (m < t_sq[:, k - 1:k]) & ~dup
        pos = jnp.sum((t_sq <= m).astype(jnp.int32), axis=1, keepdims=True)
        # jnp.roll's wrapped slot 0 is never read: slot 0 > pos cannot hold.
        t_sq = jnp.where(~enter | (slot < pos), t_sq,
                         jnp.where(slot == pos, m, jnp.roll(t_sq, 1, 1)))
        t_ids = jnp.where(~enter | (slot < pos), t_ids,
                          jnp.where(slot == pos, cid, jnp.roll(t_ids, 1, 1)))
        m, c = least((cand > m) | ((cand == m) & (col > c)))
        return t_sq, t_ids, m, c, rounds + 1

    m, c = least(jnp.ones(cand.shape, bool))
    top_sq, top_ids, _, _, rounds = jax.lax.while_loop(
        live, insert, (top_sq, top_ids, m, c, jnp.int32(0)))
    return top_sq, top_ids, rounds
