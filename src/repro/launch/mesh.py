"""Mesh construction and the ``shard_map`` entry point of the launch layer.

Functions, not module constants, so importing never touches jax device
state.

``shard_map(f, *, mesh, in_specs, out_specs, check_vma=False)``
    ``jax.shard_map`` in keyword form.

``make_mesh_compat(shape, axes, devices=None)``
    A concrete ``jax.sharding.Mesh`` with every axis ``Auto``, over
    ``devices`` (default: ``jax.devices()``; jax errors if the shape does not
    match the device count).  The serving driver passes the first N
    devices so ``--devices N`` means N on every platform.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh_compat", "make_production_mesh", "make_host_mesh", "shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh_compat(shape, axes, devices=None):
    """``jax.make_mesh`` with explicit ``Auto`` axes over ``devices``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod meshes: 16x16 = 256 chips/pod; 2 pods = 512 chips.

    Axes: 'pod' (pure DP between pods), 'data' (DP + FSDP/ZeRO),
    'model' (TP / KV-seq / ffn).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return make_mesh_compat((data, model), ("data", "model"))
