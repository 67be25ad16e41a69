"""The row-sharded flat route over a four-device mesh, at the chip
benchmark's widths (256-d rows, Δd=128, 64-query batches, k=10) and a small
corpus, in Pallas interpret mode.  Runs in a subprocess with 4 forced host
devices (the main test process must keep a single device)."""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs.dade_ivf import ServiceConfig
    from repro.core import build_estimator
    from repro.data.pipeline import synthetic_queries, synthetic_vectors
    from repro.kernels.ops import block_table
    from repro.launch.annservice import build_search_step, search_input_specs
    from repro.launch.mesh import make_mesh_compat
    from repro.obs import MetricsRegistry, record_flat_exchange
    from repro.quant import fit_block_scales, quantize_block
    from repro.quant.accounting import flat_exchange_bytes

    A, N_LOCAL, DIM, Q, K = 4, 2048, 256, 64, 10
    assert len(jax.devices()) == A
    svc = ServiceConfig(corpus_per_device=N_LOCAL, dim=DIM, query_batch=Q,
                        k=K, delta_d=128, wave=1024, p_s=0.02,
                        dtype="bfloat16", quant="int8")
    corpus = np.asarray(synthetic_vectors(A * N_LOCAL, DIM, seed=0),
                        np.float32)
    queries = np.asarray(synthetic_queries(Q, DIM, corpus, seed=1),
                         np.float32)
    est = build_estimator("dade", corpus[:4096], jax.random.PRNGKey(0),
                          p_s=svc.p_s, delta_d=svc.delta_d)
    eps, scale, d_pad, eps_lo = block_table(est.table, DIM, svc.delta_d)
    assert d_pad == DIM
    rows = np.asarray(est.rotate(jnp.asarray(corpus)))
    q_rot = jnp.asarray(np.asarray(est.rotate(jnp.asarray(queries))),
                        jnp.bfloat16)
    scales = fit_block_scales(jnp.asarray(rows), svc.delta_d)
    codes = np.asarray(quantize_block(jnp.asarray(rows), scales, svc.delta_d))

    def search(devices, lo, hi, **kw):
        mesh = make_mesh_compat((len(devices),), ("data",), devices=devices)
        s = dataclasses.replace(svc, corpus_per_device=(hi - lo) // len(devices))
        _, sh = search_input_specs(s, mesh, quant="int8", fused=True)
        fn = build_search_step(s, mesh, quant="int8", fused=True,
                               interpret=True, **kw)
        args = (jax.device_put(rows[lo:hi].astype(jnp.bfloat16), sh[0]),
                jax.device_put(codes[lo:hi], sh[1]),
                jax.device_put(scales, sh[2]), q_rot, eps, scale, eps_lo)
        d, i = jax.jit(fn, in_shardings=sh)(*args)
        return np.asarray(d), np.asarray(i), jax.jit(fn).lower(*args)

    devs = jax.devices()

    # (a) No shared seed: the mesh step is the plain merge of four
    # one-device runs, each over its shard's rows, ids offset by the
    # shard's base.  Exact: each shard screens the same rows with the same
    # thresholds either way, and both merges keep the earlier shard on ties.
    d4, i4, _ = search(devs, 0, A * N_LOCAL, two_phase=False)
    parts = [search([devs[0]], s * N_LOCAL, (s + 1) * N_LOCAL,
                    two_phase=False)[:2] for s in range(A)]
    all_d = np.concatenate([d for d, _ in parts], axis=1)
    all_i = np.concatenate([i + s * N_LOCAL for s, (_, i) in enumerate(parts)],
                           axis=1)
    order = np.argsort(all_d, axis=1, kind="stable")[:, :K]
    assert np.array_equal(i4, np.take_along_axis(all_i, order, axis=1))
    np.testing.assert_array_equal(d4, np.take_along_axis(all_d, order, axis=1))
    print("OK merge_exact")

    # (b) Serving settings: every returned distance is the distance to the
    # raw row of the returned global id.  The kernel's exact stage works on
    # bfloat16 rows and queries, each element within 2^-8 of its float32
    # value, so |d_bf16 - d_f32| <= 2^-8 (|x| + |q|); an id of another
    # shard or offset lands on an unrelated row, tens of percent away.
    d, ids, lowered = search(devs, 0, A * N_LOCAL)
    assert ids.min() >= 0 and ids.max() < A * N_LOCAL
    assert all(len(set(r)) == K for r in ids.tolist())
    x = corpus[ids]
    true_d = np.sqrt(np.sum((x - queries[:, None, :]) ** 2, axis=-1))
    tol = 2.0 ** -8 * (np.linalg.norm(x, axis=-1)
                       + np.linalg.norm(queries, axis=-1)[:, None])
    assert np.all(np.abs(d - true_d) <= tol), np.max(np.abs(d - true_d) / tol)
    # Recall against exact float32 search.  The floor: DADE's screen at
    # p_s = 0.02 may drop a true neighbour at each checkpoint, and bf16
    # rounding reorders near-ties at the 10th place.
    ex = np.sum((corpus[None] - queries[:, None]) ** 2, axis=-1)
    gt = np.argsort(ex, axis=1)[:, :K]
    recall = np.mean([len(set(a) & set(b)) / K for a, b in zip(ids, gt)])
    assert recall >= 0.95, recall
    print("OK served_distances", recall)

    # (c) The exchange is named in the lowered four-device step: the
    # scopes are the locations of its collectives.
    text = lowered.as_text(debug_info=True)
    assert 'loc("exchange.seed/pmin"' in text
    assert 'loc("exchange.merge/all_gather"' in text
    print("OK named_exchange")

    # (d) The exchange ledger: seed pmin plus one merge hop, received by
    # each device from its 3 peers; nothing on one device.
    per_hop = Q * K * (4 + 4) + Q * 4
    assert flat_exchange_bytes(axis_sizes=(4,), queries=Q, k=K) == 4 * 3 * per_hop
    assert flat_exchange_bytes(axis_sizes=(1,), queries=Q, k=K) == 0
    assert flat_exchange_bytes(axis_sizes=(2, 2), queries=Q, k=K) == 2 * 4 * per_hop
    reg = MetricsRegistry()
    for _ in range(3):
        record_flat_exchange(reg, exchanged_bytes=4 * 3 * per_hop)
    assert reg.counter("dco.exchanged.bytes").value == 3 * 4 * 3 * per_hop
    print("OK exchange_bytes")

    # (e) serve.py's flat route feeds that ledger once per timed step: the
    # stated bytes a step on four devices, nothing on one.
    import contextlib, io, json, re, tempfile
    from repro.launch import serve
    for n_dev in (4, 1):
        path = os.path.join(tempfile.mkdtemp(), "metrics.json")
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            serve.main(["--devices", str(n_dev), "--requests", "3",
                        "--corpus-per-device", "4096", "--dim", "64",
                        "--batch", "32", "--metrics-json", path])
        batches = int(re.search(r" batches=(\\d+) ", out.getvalue()).group(1))
        got = json.load(open(path))["metrics"]["dco.exchanged.bytes"]["value"]
        assert batches > 0 and got == batches * flat_exchange_bytes(
            axis_sizes=(n_dev,), queries=32, k=10), (n_dev, batches, got)
    print("OK serve_ledger")
""")


@pytest.fixture(scope="module")
def script_out():
    """One run of the script for every check."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(root, "src"),
             "JAX_PLATFORMS": "cpu"},
        cwd=root, timeout=300)
    return r


# The script runs the checks in this order and stops at the first failure.
@pytest.mark.parametrize("check", [
    "merge_exact", "served_distances", "named_exchange", "exchange_bytes",
    "serve_ledger"])
def test_flat_route_on_four_devices(script_out, check):
    r = script_out
    assert f"OK {check}" in r.stdout, (
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}")
