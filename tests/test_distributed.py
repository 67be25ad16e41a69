"""Multi-device semantics, run in a subprocess with 8 forced host devices
(the main test process must keep a single device)."""

import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding

    from repro.configs.dade_ivf import ServiceConfig
    from repro.launch.mesh import make_mesh_compat, shard_map
    from repro.core import build_estimator, exact_knn
    from repro.data.pipeline import synthetic_vectors, synthetic_queries
    from repro.distributed.collectives import (
        compressed_grad_allreduce, hierarchical_topk)
    from repro.kernels.ops import block_table
    from repro.launch.annservice import build_search_step, search_input_specs
    from repro.checkpoint.manager import CheckpointManager
    from repro.distributed.sharding import tree_shardings

    assert len(jax.devices()) == 8
    mesh = make_mesh_compat((4, 2), ("data", "model"))

    # ---- 1. distributed DADE search == single-device exact topk ------------
    svc = ServiceConfig(corpus_per_device=2048, dim=64, query_batch=16, k=10,
                        delta_d=32, wave=1024, p_s=0.02)
    n = 8 * svc.corpus_per_device
    corpus = synthetic_vectors(n, svc.dim, seed=0)
    queries = synthetic_queries(16, svc.dim, corpus, seed=1)
    est = build_estimator("dade", corpus[:8000], jax.random.PRNGKey(0),
                          p_s=svc.p_s, delta_d=svc.delta_d)
    eps, scale, d_pad, eps_lo = block_table(est.table, svc.dim, svc.delta_d)
    c_rot = np.pad(np.asarray(est.rotate(jnp.asarray(corpus))),
                   ((0, 0), (0, d_pad - svc.dim)))
    q_rot = np.pad(np.asarray(est.rotate(jnp.asarray(queries))),
                   ((0, 0), (0, d_pad - svc.dim)))
    _, shardings = search_input_specs(svc, mesh)
    step = jax.jit(build_search_step(svc, mesh), in_shardings=shardings)
    dists, ids = step(jax.device_put(c_rot, shardings[0]), jnp.asarray(q_rot),
                      eps, scale, eps_lo)
    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(corpus), 10)
    ids, gt = np.asarray(ids), np.asarray(gt)
    recall = np.mean([len(set(ids[i]) & set(gt[i])) / 10 for i in range(16)])
    assert recall >= 0.95, f"distributed search recall {recall}"
    print("OK distributed_search", recall)

    # ---- 1b. quantized serving path (repro.quant, --quant int8) -------------
    from repro.quant import quantize_corpus
    _, sh_q = search_input_specs(svc, mesh, quant="int8")
    step_q = jax.jit(build_search_step(svc, mesh, quant="int8"),
                     in_shardings=sh_q)
    qcorp = quantize_corpus(jnp.asarray(c_rot))
    dists_q, ids_q = step_q(
        jax.device_put(c_rot, sh_q[0]),
        jax.device_put(np.asarray(qcorp.codes), sh_q[1]),
        jax.device_put(np.asarray(qcorp.scales), sh_q[2]),
        jnp.asarray(q_rot), eps, scale, eps_lo)
    ids_q = np.asarray(ids_q)
    recall_q = np.mean([len(set(ids_q[i]) & set(gt[i])) / 10 for i in range(16)])
    assert recall_q >= recall - 0.02, (
        f"quant serving recall {recall_q} vs fp {recall}")
    print("OK quant_search", recall_q)

    # ---- 1c. fused megakernel serving route (interpret mode off-TPU) --------
    from repro.quant import fit_block_scales, quantize_block
    _, sh_f = search_input_specs(svc, mesh, quant="int8", fused=True)
    step_f = jax.jit(build_search_step(svc, mesh, quant="int8", fused=True),
                     in_shardings=sh_f)
    bscales = fit_block_scales(jnp.asarray(c_rot), svc.delta_d)
    bcodes = quantize_block(jnp.asarray(c_rot), bscales, svc.delta_d)
    dists_f, ids_f = step_f(
        jax.device_put(c_rot, sh_f[0]),
        jax.device_put(np.asarray(bcodes), sh_f[1]),
        jax.device_put(np.asarray(bscales), sh_f[2]),
        jnp.asarray(q_rot), eps, scale, eps_lo)
    ids_f = np.asarray(ids_f)
    recall_f = np.mean([len(set(ids_f[i]) & set(gt[i])) / 10 for i in range(16)])
    assert recall_f >= recall - 0.02, (
        f"fused serving recall {recall_f} vs fp {recall}")
    print("OK fused_search", recall_f)

    # ---- 1d. corpus-sharded graph serving (cross-shard frontier exchange) ---
    # The acceptance property over a REAL 2-device mesh: the shard_map'd
    # wave step (local beam-scan launches + all-gathered window/bitmap
    # merge) returns bit-identical ids to the single-host beam oracle on
    # the unsharded corpus, and the per-shard fetch ledgers sum to the
    # single-host ledger.
    from repro.index.graph import build_graph, search_graph_sharded
    from repro.launch.annservice import build_sharded_graph_engine

    gsub = np.asarray(corpus)[:800]
    gidx = build_graph(gsub, m=10, ef_construction=32, delta_d=32,
                       quant="int8")
    gmesh = make_mesh_compat((2,), ("shard",))
    gq = synthetic_queries(16, svc.dim, gsub, seed=5)
    engine = build_sharded_graph_engine(gidx, gmesh, k=10, ef=24,
                                        block_q=8, with_stats=True)
    gd, gi, gst = engine(np.asarray(gq, np.float32))
    od, oi, ost = search_graph_sharded(gidx, jnp.asarray(gq), num_shards=1,
                                       k=10, ef=24, block_q=8, use_ref=True)
    assert np.array_equal(gi, np.asarray(oi)), "sharded graph != oracle"
    np.testing.assert_allclose(gd, np.asarray(od), rtol=1e-5, atol=1e-5)
    assert gst.num_shards == 2 and gst.waves == ost.waves
    assert (sum(gst.shard_s1_tiles_fetched)
            == sum(ost.shard_s1_tiles_fetched))
    assert gst.exchange_bytes_per_wave > 0
    print("OK sharded_graph", gst.waves, gst.exchange_bytes_per_wave)

    # ---- 2. hierarchical_topk == flat global top-k --------------------------
    rng = np.random.default_rng(0)
    local = np.sort(rng.random((8, 4, 6)).astype(np.float32), axis=2)  # dev,Q,K
    lids = rng.integers(0, 10000, (8, 4, 6)).astype(np.int32)
    def merge(sq, ids):
        return hierarchical_topk(sq[0], ids[0], ("model", "data"), 6)
    out_sq, out_ids = shard_map(
        merge, mesh=mesh,
        in_specs=(P(("data", "model")), P(("data", "model"))),
        out_specs=(P(), P()), check_vma=False,
    )(jnp.asarray(local), jnp.asarray(lids))
    ref = np.sort(local.transpose(1, 0, 2).reshape(4, 48), axis=1)[:, :6]
    np.testing.assert_allclose(np.asarray(out_sq), ref, rtol=1e-6)
    print("OK hierarchical_topk")

    # ---- 3. int8 compressed all-reduce ~ mean --------------------------------
    g = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 64.0}
    e = {"w": jnp.zeros((8, 8), jnp.float32)}
    def comp(gg, ee):
        return compressed_grad_allreduce(gg, ee, "data")
    # replicated grads: the mean over identical shards must return the input
    # up to int8 quantization error (max|g|/127)
    mean_g, new_e = shard_map(
        comp, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P(), P()), check_vma=False)(g, e)
    err = float(jnp.max(jnp.abs(mean_g["w"] - g["w"])))
    assert err < 0.01, f"quantized allreduce err {err}"
    # error feedback holds the residual: g ~ dequant + e
    recon = float(jnp.max(jnp.abs(mean_g["w"] + new_e["w"] - g["w"])))
    assert recon < 1e-5, f"error feedback broken: {recon}"
    print("OK compressed_allreduce", err)

    # ---- 4. elastic restore onto a different mesh ----------------------------
    import tempfile
    tree = {"w": jnp.arange(32.0).reshape(4, 8)}
    sh1 = NamedSharding(mesh, P("data", "model"))
    t1 = jax.device_put(tree, {"w": sh1})
    mgr = CheckpointManager(tempfile.mkdtemp(), async_save=False)
    mgr.save(1, t1)
    mesh2 = make_mesh_compat((8,), ("data",))
    sh2 = {"w": NamedSharding(mesh2, P(None, "data"))}
    t2 = mgr.restore(1, tree, shardings=sh2)
    np.testing.assert_array_equal(np.asarray(t2["w"]), np.asarray(tree["w"]))
    assert t2["w"].sharding == sh2["w"]
    print("OK elastic_restore")
""")


@pytest.mark.slow
def test_distributed_semantics():
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=".", timeout=540,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    for marker in ("OK distributed_search", "OK quant_search",
                   "OK fused_search", "OK sharded_graph",
                   "OK hierarchical_topk", "OK compressed_allreduce",
                   "OK elastic_restore"):
        assert marker in r.stdout
