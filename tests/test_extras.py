"""Extended coverage: census parser, baseline L2 kernel, MoE invariants,
request scheduler, int8 KV cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.launch.hlo_census import census


# ---- HLO census ------------------------------------------------------------

def test_census_counts_scan_trips():
    """Known scanned matmul: census flops must equal the analytic count."""
    L, B, D, F = 6, 8, 32, 64

    def step(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()

    comp = jax.jit(step).lower(
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32)).compile()
    cen = census(comp.as_text())
    assert cen["flops"] == 2 * L * B * D * D
    assert list(cen["loops"].values()) == [L]


def test_census_nested_scan_multiplies():
    L1, L2, B, D = 3, 4, 4, 16

    def step(w, x):
        def outer(x, _):
            def inner(x, _):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(inner, x, None, length=L2)
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=L1)
        return x.sum()

    comp = jax.jit(step).lower(
        jax.ShapeDtypeStruct((D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32)).compile()
    cen = census(comp.as_text())
    assert cen["flops"] == 2 * L1 * L2 * B * D * D


# ---- baseline L2 kernel -----------------------------------------------------

@pytest.mark.parametrize("d", [128, 256])
def test_l2_scan_kernel_exact(d):
    from repro.kernels.l2_scan import l2_scan_kernel_call
    rng = np.random.default_rng(0)
    q = rng.standard_normal((8, d)).astype(np.float32)
    c = rng.standard_normal((256, d)).astype(np.float32)
    out = l2_scan_kernel_call(
        jnp.asarray(q), jnp.asarray(c), block_q=8, block_c=128, block_d=128,
        interpret=True)
    ref = ((q[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=1e-3)


def test_dade_kernel_never_exceeds_l2_work():
    """DADE's dims_used <= full D everywhere; strict subset when r is tight."""
    from repro.core import build_estimator
    from repro.kernels.ops import dco_screen_kernel
    rng = np.random.default_rng(1)
    scales = np.exp(-0.06 * np.arange(128)).astype(np.float32)
    data = (rng.standard_normal((2048, 128)) * scales).astype(np.float32)
    est = build_estimator("dade", data, jax.random.PRNGKey(0), delta_d=32)
    q = est.rotate(jnp.asarray(data[:8]))
    c = est.rotate(jnp.asarray(data[:512]))
    _, _, dims = dco_screen_kernel(est, q, c, jnp.full((8,), 1.0),
                                   interpret=True, block_d=32)
    assert int(np.max(np.asarray(dims))) <= 128
    assert float(np.mean(np.asarray(dims))) < 128  # pruning happened


# ---- MoE invariants ----------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), s=st.sampled_from([16, 32]),
       e=st.sampled_from([4, 8]), k=st.integers(1, 3))
def test_moe_dispatch_invariants(seed, s, e, k):
    """Capacity respected; output is a convex-ish combination (bounded by
    the max expert output norm) and zero tokens stay zero."""
    from repro.configs import reduced_config
    from repro.models.common import Initializer
    from repro.models.moe import init_moe, moe_fwd
    from repro.models.common import split_tree

    cfg = dataclasses.replace(
        reduced_config("mixtral-8x7b"), num_experts=e, experts_per_tok=k,
        d_model=32, moe_d_ff=64, d_ff=64)
    init = Initializer(jax.random.PRNGKey(seed), jnp.float32)
    params, _ = split_tree(init_moe(init, cfg))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, s, 32))
    y, aux = moe_fwd(params, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(aux) >= 0.99  # load-balance loss lower bound is ~1 at E*f.p

    # zero input -> zero routed output modulo router bias (no bias here)
    y0, _ = moe_fwd(params, jnp.zeros_like(x), cfg)
    assert float(jnp.max(jnp.abs(y0))) < 1e-5


def test_moe_capacity_drops_are_bounded():
    """With cf>=k (capacity >= all tokens), nothing is dropped: output equals
    a dense per-token mixture computed independently."""
    from repro.configs import reduced_config
    from repro.models.common import Initializer, split_tree
    from repro.models.moe import init_moe, moe_fwd

    cfg = dataclasses.replace(
        reduced_config("mixtral-8x7b"), num_experts=4, experts_per_tok=2,
        d_model=16, moe_d_ff=32, d_ff=32, capacity_factor=4.0)
    init = Initializer(jax.random.PRNGKey(0), jnp.float32)
    params, _ = split_tree(init_moe(init, cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
    y, _ = moe_fwd(params, x, cfg)

    # dense reference: every token through its top-k experts
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gv, ei = jax.lax.top_k(probs, 2)
    gv = gv / jnp.sum(gv, axis=-1, keepdims=True)
    ref = jnp.zeros_like(x)
    for b in range(1):
        for t in range(8):
            for j in range(2):
                eidx = int(ei[b, t, j])
                h = jax.nn.silu(x[b, t] @ params["w_gate"][eidx]) * (
                    x[b, t] @ params["w_up"][eidx])
                ref = ref.at[b, t].add(gv[b, t, j] * (h @ params["w_down"][eidx]))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


# ---- request scheduler --------------------------------------------------------

def test_batch_scheduler_packs_and_scatters():
    from repro.runtime.scheduler import BatchScheduler

    calls = []

    def step(batch):
        calls.append(batch.shape)
        s = batch.sum(axis=1, keepdims=True)
        return np.repeat(s, 3, 1), np.tile(np.arange(3), (len(batch), 1))

    sched = BatchScheduler(step, batch_size=4)
    r1 = sched.submit(np.ones((3, 8)))
    r2 = sched.submit(2 * np.ones((6, 8)))
    done = sched.drain()
    assert {r.rid for r in done} == {r1.rid, r2.rid}
    assert r1.result[0].shape == (3, 3)
    assert r2.result[0].shape == (6, 3)
    np.testing.assert_allclose(r1.result[0], 8.0)
    np.testing.assert_allclose(r2.result[0], 16.0)
    assert all(s == (4, 8) for s in calls)  # fixed compiled batch shape
    assert sched.stats["padded_rows"] == 4 * len(calls) - 9


def test_batch_scheduler_respects_latency_bound():
    from repro.runtime.scheduler import BatchScheduler
    sched = BatchScheduler(lambda b: (b[:, :1], b[:, :1].astype(int)),
                           batch_size=8, max_wait_s=0.0)
    sched.submit(np.ones((2, 4)))
    done = sched.drain(force=False)  # max_wait 0 -> flush immediately
    assert len(done) == 1


def test_batch_scheduler_counts_queue_wait(monkeypatch):
    """wait_s sums dispatch start minus enqueue over the dispatched rows;
    rows shed at dispatch add nothing; the registry counter matches."""
    import time

    from repro.obs import MetricsRegistry
    from repro.runtime.scheduler import BatchScheduler

    now = [12.0]
    monkeypatch.setattr(time, "perf_counter", lambda: now[0])

    def step(batch):  # every engine step takes one second of the clock
        now[0] += 1.0
        return batch[:, :1], np.zeros((len(batch), 1), np.int32)

    reg = MetricsRegistry()
    sched = BatchScheduler(step, batch_size=4, registry=reg)
    r1 = sched.submit(np.ones((3, 2)))
    r2 = sched.submit(np.ones((6, 2)))
    late = sched.submit(np.ones((2, 2)))
    r1.enqueued_at, r2.enqueued_at, late.enqueued_at = 10.0, 11.0, 11.0
    late.deadline_at = 11.5
    done = sched.drain()
    assert [r.rid for r in done] == [r1.rid, r2.rid]
    assert late.status == "shed_deadline"
    # Batch 1 starts at 12: r1 x3 waited 2 s, r2 x1 waited 1 s.  Batch 2
    # at 13: r2 x4, 2 s each.  Batch 3 at 14: r2 x1, 3 s; both rows of
    # the late request are shed there and count nothing.
    want = 3 * 2.0 + 1 * 1.0 + 4 * 2.0 + 1 * 3.0
    assert sched.stats["rows"] == 9 and sched.stats["batches"] == 3
    assert sched.stats["wait_s"] == want
    assert reg.counter("serve.queue.wait_s").value == want


# ---- int8 KV cache -------------------------------------------------------------

def test_int8_kv_cache_close_to_bf16():
    from repro.configs import reduced_config
    from repro.models.model import build_model

    base = dataclasses.replace(reduced_config("codeqwen1.5-7b"),
                               kv_cache_dtype="")
    q8 = dataclasses.replace(base, kv_cache_dtype="int8")
    m, m8 = build_model(base), build_model(q8)
    params, _ = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, base.vocab_size)
    c1, _ = m.init_caches(2, 12)
    c2, _ = m8.init_caches(2, 12)
    assert c2["kv0"].k.dtype == jnp.int8
    s1, s2 = jax.jit(m.decode_step), jax.jit(m8.decode_step)
    for t in range(12):
        l1, c1 = s1(params, toks[:, t:t + 1], c1, jnp.asarray(t, jnp.int32))
        l2, c2 = s2(params, toks[:, t:t + 1], c2, jnp.asarray(t, jnp.int32))
    p1 = jax.nn.softmax(l1[:, : base.vocab_size])
    p2 = jax.nn.softmax(l2[:, : base.vocab_size])
    assert float(jnp.max(jnp.abs(p1 - p2))) < 0.02
