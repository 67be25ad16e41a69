"""Pallas TPU spellings shared by every kernel in this repo.

``CompilerParams``
    The TPU compiler-params class passed to ``pl.pallas_call`` (used with
    ``dimension_semantics=(...)`` of ``"parallel"``/``"arbitrary"``).

``ANY_MEMSPACE``
    The "HBM-resident, let the kernel page it manually" memory space, used
    as ``pl.BlockSpec(memory_space=ANY_MEMSPACE)`` for the corpus streams
    the megakernels DMA themselves: the operand is not BlockSpec-pipelined,
    the kernel sees an HBM ref it must ``pltpu.make_async_copy`` from.
"""

from __future__ import annotations

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams
ANY_MEMSPACE = pl.ANY

__all__ = ["CompilerParams", "ANY_MEMSPACE"]
