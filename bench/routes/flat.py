"""The flat route: ``launch/serve.py``'s flat serve.

``build_search_step(quant="int8", fused=True)`` over a one-axis mesh of the
cell's chips: int8 block codes and bfloat16 rotated rows, row-sharded, with
the hierarchical top-K merge across chips.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from engine import Engine


def _estimator(cfg, blocks, seed):
    from repro.core import build_estimator

    sample = blocks[0][: cfg["estimator_sample"]]
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (1 << 31))
    return build_estimator(cfg["method"], sample, key, p_s=cfg["p_s"],
                           delta_d=cfg["delta_d"])


def build(cfg, blocks, devices, seed, *, interpret):
    from repro.configs.dade_ivf import ServiceConfig
    from repro.kernels.ops import block_table
    from repro.launch.annservice import build_search_step, search_input_specs
    from repro.quant import fit_block_scales, quantize_block

    dim, dd = cfg["dim"], cfg["delta_d"]
    mesh = Mesh(np.asarray(devices), ("data",))
    svc = dataclasses.replace(
        ServiceConfig(), corpus_per_device=cfg["corpus_per_device"], dim=dim,
        query_batch=cfg["query_batch"], k=cfg["k"], delta_d=dd,
        wave=cfg["wave"], p_s=cfg["p_s"], dtype=cfg["dtype"], quant="int8")
    repl = NamedSharding(mesh, P())
    est = jax.device_put(_estimator(cfg, blocks, seed), repl)
    eps, scale, d_pad, eps_lo = block_table(est.table, dim, dd)
    _, shardings = search_input_specs(svc, mesh, quant="int8", fused=True)
    row_shard = NamedSharding(mesh, P("data", None))
    corpus = jax.make_array_from_single_device_arrays(
        (len(blocks) * blocks[0].shape[0], dim), row_shard, blocks)
    c_rot = est.rotate(corpus)
    if d_pad != dim:
        c_rot = jnp.pad(c_rot, ((0, 0), (0, d_pad - dim)))
    bscales = fit_block_scales(c_rot, dd)
    state = {
        "rows": jax.device_put(c_rot.astype(svc.dtype), shardings[0]),
        "codes": jax.device_put(quantize_block(c_rot, bscales, dd), shardings[1]),
        "scales": jax.device_put(bscales, shardings[2]),
    }
    del c_rot, corpus
    search = jax.jit(build_search_step(svc, mesh, quant="int8", fused=True,
                                       interpret=interpret),
                     in_shardings=shardings)
    rotate = jax.jit(
        lambda q: jnp.pad(est.rotate(q), ((0, 0), (0, d_pad - dim))
                          ).astype(svc.dtype), out_shardings=repl)

    def step(batch):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                d, i = search(state["rows"], state["codes"], state["scales"],
                              rotate(batch), eps, scale, eps_lo)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                return np.asarray(d), np.asarray(i)

    return Engine(step=step, batch=svc.query_batch, free=state.clear,
                  notes={"d_pad": d_pad, "rows_per_chip": blocks[0].shape[0]})
