"""Compile-only checks of the serving kernels for a TPU v5e that is
described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes off the
tiling grid, fast-memory overruns, unsupported dot precisions), so these
tests lower and compile the megakernels and the flat fused search step at
serving widths — 1M rows x 256 dims, Δd=128, the IVF route's 32 x 128
tiles and the flat route's wider ones — and
check that the Pallas kernel is in the compiled program.  Nothing runs.

The topology and everything built from it lives in fixtures of this file:
describing it loads the TPU library, which must happen inside a test.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

ROWS = 1 << 20
DIM = 256
BLOCK_D = 128
BLOCK_Q = 32
S_STEPS = DIM // BLOCK_D


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        desc = None
        reason = f"no v5e:2x2 topology can be described here: {e}"
    if desc is not None:
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    if desc is None:
        pytest.skip(reason)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k,flat", [
    pytest.param(10, False, id="10"), pytest.param(100, False, id="100"),
    pytest.param(10, True, id="flat-10")])
def test_ivf_scan_compiles_at_serving_width(one_chip, k, flat):
    """At the IVF route's (32, 128) tile, and at the tile the flat route's
    rule picks for the serving batch (64 queries, wave 4096, bf16 rows)."""
    from repro.kernels.ivf_scan import ivf_scan_kernel_call
    from repro.kernels.ops import flat_tile_shape

    qn, wave = 64, 4096
    block_q, block_c = (flat_tile_shape(qn, wave, DIM, jnp.bfloat16)
                        if flat else (BLOCK_Q, 128))
    cap_tiles = wave // block_c
    s = functools.partial(_sds, sharding=one_chip)
    args = (
        s((qn // block_q, ROWS // wave, cap_tiles), jnp.int32),  # tile offs
        s((qn, DIM), jnp.int8), s((qn, DIM), jnp.float32),
        s((qn, S_STEPS), jnp.float32), s((qn,), jnp.float32),
        s((qn, k), jnp.float32), s((qn, k), jnp.int32),
        s((ROWS, DIM), jnp.int8), s((ROWS, DIM), jnp.bfloat16),
        s((ROWS,), jnp.int32),
        s((S_STEPS,), jnp.float32), s((S_STEPS,), jnp.float32),
        s((S_STEPS,), jnp.float32),
    )
    fn = functools.partial(
        ivf_scan_kernel_call, k=k, block_q=block_q, block_c=block_c,
        block_d=BLOCK_D, cap_tiles=cap_tiles, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("q_tiles,tighten", [(1, True), (2, True),
                                             (2, False)])
def test_graph_scan_compiles_with_narrow_adjacency_block(one_chip, q_tiles,
                                                         tighten):
    """q_tiles >= 2 and adj_block=32 were both refused before the bitmap and
    id blocks got a leading per-tile axis."""
    from repro.kernels.graph_scan import graph_scan_kernel_call
    from repro.kernels.ops import graph_vis_words

    nodes, a_block, ef, steps = 16384, 32, 48, 64
    qn = q_tiles * BLOCK_Q
    words = graph_vis_words(nodes)
    s = functools.partial(_sds, sharding=one_chip)
    args = (
        s((q_tiles, steps), jnp.int32),
        s((qn, DIM), jnp.int8), s((qn, DIM), jnp.float32),
        s((qn, S_STEPS), jnp.float32),
        s((qn, ef), jnp.float32), s((qn, ef), jnp.int32),
        s((qn,), jnp.float32), s((q_tiles, words), jnp.int32),
        s((nodes * a_block, DIM), jnp.int8),
        s((nodes * a_block, DIM), jnp.bfloat16),
        s((nodes * a_block,), jnp.int32),
        s((S_STEPS,), jnp.float32), s((S_STEPS,), jnp.float32),
        s((S_STEPS,), jnp.float32),
    )
    fn = functools.partial(
        graph_scan_kernel_call, ef=ef, thresh_col=9, block_q=BLOCK_Q,
        block_c=a_block, block_d=BLOCK_D, tighten=tighten, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("chips", [1, 4])
def test_flat_fused_search_step_compiles(topo, chips):
    """The jitted step serve.py builds for ``--quant int8 --fused on`` on
    TPU, at 1M rows per chip, on one described chip and on a 4-chip mesh."""
    from repro.configs.dade_ivf import ServiceConfig
    from repro.launch.annservice import build_search_step, search_input_specs

    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    svc = ServiceConfig(corpus_per_device=ROWS, dim=DIM, query_batch=64, k=10,
                        delta_d=BLOCK_D, wave=4096, quant="int8")
    shapes, shardings = search_input_specs(svc, mesh, quant="int8",
                                           fused=True)
    args = [_sds(x.shape, x.dtype, sh) for x, sh in zip(shapes, shardings)]
    step = build_search_step(svc, mesh, quant="int8", fused=True,
                             with_stats=True, interpret=False)
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The cross-chip exchange is named; on one chip it folds away.
    named = ("exchange.seed/pmin" in text, "exchange.merge/all_gather" in text)
    assert named == ((True, True) if chips > 1 else (False, False))
    # corpus rows (bf16) + int8 codes per chip, as serve places them
    per_chip = ROWS * DIM * (jnp.dtype(svc.dtype).itemsize + 1)
    assert compiled.memory_analysis().argument_size_in_bytes >= per_chip
    if chips > 1:
        assert isinstance(shardings[0], NamedSharding)
        assert shardings[0].spec == P(("data",), None)
