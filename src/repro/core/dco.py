"""Batched distance-comparison-operation (DCO) engine — TPU adaptation of
Algorithm 1.

The paper's per-candidate loop (grow d by Δd, test, early-exit) is rephrased
as a *block-incremental masked screen* over a tile of candidates:

    for each checkpoint d_s in (Δd, 2Δd, ..., D):
        psum  += ||(q' - o')[d_{s-1}:d_s]||²        (only rows still active)
        est²   = psum · scale_s
        prune  = est² > (1+eps_s)² · r²             (reject H0)
        active &= ~prune ; dims_used updated

Rows that survive to d=D hold the *exact* squared distance in ``psum``
(scale_S = 1), matching Algorithm 1 line 13.  ``dims_used`` records the
checkpoint at which each row retired — the quantity the paper plots on the
x-axis of Fig. 3 and the proxy for FLOPs actually spent.

This module is the pure-jnp functional definition (also the oracle for the
Pallas kernel in ``repro.kernels``).  XLA computes all D dims here — the
*work skipping* is realized by the Pallas kernel's tile-granular early exit
and by the numpy compaction engine (``dco_host``) used for CPU wall-clock
benchmarks; all three agree on outputs bit-for-bit up to dtype.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.calibration import EpsilonTable

__all__ = ["DCOResult", "dco_screen", "dco_screen_batch"]


class DCOResult(NamedTuple):
    """Outcome of a batched DCO screen.

    est_sq: (C,) final squared distance estimate per candidate (exact for
      rows that reached d=D; the rejecting estimate for pruned rows).
    passed: (C,) bool — Algorithm-1 "return 1": survived every test AND the
      terminal (exact or fixed-dim) estimate is <= r.
    dims_used: (C,) int32 — dimensions consumed before retirement.
    """

    est_sq: jax.Array
    passed: jax.Array
    dims_used: jax.Array


@partial(jax.jit, donate_argnums=())
def dco_screen(
    q_rot: jax.Array,  # (D,) rotated query
    cands_rot: jax.Array,  # (C, D) rotated candidates
    table: EpsilonTable,
    r_sq: jax.Array,  # scalar squared threshold
) -> DCOResult:
    """Screen C candidates against threshold r for a single query."""
    diff = cands_rot - q_rot[None, :]
    sq = diff * diff  # (C, D)
    csq = jnp.cumsum(sq.astype(jnp.float32), axis=1)  # (C, D)
    return _screen_from_cumsum(csq, table, r_sq)


def _screen_from_cumsum(csq: jax.Array, table: EpsilonTable, r_sq: jax.Array) -> DCOResult:
    dims = table.dims  # (S,)
    partial_sq = csq[:, dims - 1]  # (C, S): ||W_d^T dx||^2 at each checkpoint
    est_sq_all = partial_sq * table.scale[None, :]  # (C, S)
    thresh = (1.0 + table.eps) ** 2 * r_sq  # (S,)
    reject = est_sq_all > thresh[None, :]  # (C, S)

    # First checkpoint at which H0 is rejected; S (=none) if never rejected.
    s_idx = jnp.arange(dims.shape[0])
    first_reject = jnp.min(
        jnp.where(reject, s_idx[None, :], dims.shape[0]), axis=1
    )  # (C,)
    never = first_reject == dims.shape[0]
    retire_s = jnp.where(never, dims.shape[0] - 1, first_reject)

    est_sq = jnp.take_along_axis(est_sq_all, retire_s[:, None], axis=1)[:, 0]
    dims_used = dims[retire_s]
    # Algorithm 1 line 13: at the terminal checkpoint compare est vs r.
    passed = never & (est_sq <= r_sq)
    return DCOResult(est_sq=est_sq, passed=passed, dims_used=dims_used)


@partial(jax.jit)
def dco_screen_batch(
    q_rot: jax.Array,  # (Q, D) rotated queries
    cands_rot: jax.Array,  # (C, D) rotated candidates (shared across queries)
    table: EpsilonTable,
    r_sq: jax.Array,  # (Q,) per-query squared thresholds
) -> DCOResult:
    """Vectorized over a query batch: returns (Q, C)-shaped fields.

    Uses the matmul decomposition ||q-o||² = ||q||² + ||o||² - 2 q·o per
    dimension *block* so the inner product runs on the MXU — this is the
    TPU-native formulation (DESIGN.md §3.4); the cumulative structure is
    recovered blockwise.
    """
    dims = table.dims
    q = q_rot.astype(jnp.float32)
    c = cands_rot.astype(jnp.float32)

    # Blockwise partial inner products / norms at each checkpoint.
    # csq[:, :, s] = ||(q - o)[:d_s]||^2 computed via cumulative matmuls.
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), dims[:-1]])

    def block_term(start, stop):
        # Static slicing is impossible with traced bounds; instead mask.
        k = jnp.arange(q.shape[1])
        m = ((k >= start) & (k < stop)).astype(jnp.float32)
        qm = q * m[None, :]
        cm = c * m[None, :]
        dot = jnp.matmul(qm, cm.T, precision=jax.lax.Precision.HIGHEST)
        qn = jnp.sum(qm * qm, axis=1)  # (Q,)
        cn = jnp.sum(cm * cm, axis=1)  # (C,)
        return qn[:, None] + cn[None, :] - 2.0 * dot

    blocks = jax.vmap(block_term)(starts, dims)  # (S, Q, C)
    csq = jnp.cumsum(blocks, axis=0)  # (S, Q, C)
    csq = jnp.maximum(csq, 0.0)

    est_sq_all = csq * table.scale[:, None, None]
    thresh = (1.0 + table.eps[:, None, None]) ** 2 * r_sq[None, :, None]
    reject = est_sq_all > thresh

    s_count = dims.shape[0]
    s_idx = jnp.arange(s_count)
    first_reject = jnp.min(
        jnp.where(reject, s_idx[:, None, None], s_count), axis=0
    )  # (Q, C)
    never = first_reject == s_count
    retire_s = jnp.where(never, s_count - 1, first_reject)

    est_sq = jnp.take_along_axis(
        jnp.moveaxis(est_sq_all, 0, -1), retire_s[..., None], axis=-1
    )[..., 0]
    dims_used = dims[retire_s]
    passed = never & (est_sq <= r_sq[:, None])
    return DCOResult(est_sq=est_sq, passed=passed, dims_used=dims_used)
