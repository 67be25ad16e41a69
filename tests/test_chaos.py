"""Chaos injection, shard failover, load shedding, and serving snapshots.

The tentpole contract under test: with chaos DISABLED every engine is
bit-identical to a build without the chaos module (null-object hooks), and
with a shard killed the sharded graph walk keeps serving, bit-identical to
the surviving-corpus oracle (``num_shards=1, use_ref=True`` with the same
tombstones).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.chaos import (
    NULL_CHAOS, ChaosController, ChaosError, FaultSpec, current_chaos,
    parse_chaos, parse_fault, set_chaos, use_chaos)
from repro.runtime.scheduler import BatchScheduler

# ---- spec parsing ----------------------------------------------------------


def test_parse_fault_kinds_and_defaults():
    f = parse_fault("shard_death:shard=1:after=2")
    assert (f.kind, f.shard, f.after, f.count) == ("shard_death", 1, 2, -1)
    f = parse_fault("shard_stall:ms=40:after=1:count=3")
    assert (f.ms, f.count) == (40.0, 3)
    assert parse_fault("step_error").count == 1  # discrete default
    assert parse_fault("queue_overload:rows=512").count == -1  # state default


@pytest.mark.parametrize("bad", [
    "flaky_disk",                 # unknown kind
    "shard_death",                # missing shard=
    "shard_stall",                # missing ms=
    "queue_overload",             # missing rows=
    "shard_death:shard",          # not key=val
    "shard_death:shard=1:volts=9",  # unknown field
])
def test_parse_fault_rejects_bad_specs(bad):
    with pytest.raises(ValueError):
        parse_fault(bad)


def test_parse_chaos_multi_fault():
    c = parse_chaos("shard_death:shard=0;step_error:after=1:count=2")
    assert [s.kind for s in c.specs] == ["shard_death", "step_error"]
    with pytest.raises(ValueError, match="names no faults"):
        parse_chaos(" ; ")


# ---- null-object contract --------------------------------------------------


def test_null_chaos_is_inert_and_default():
    assert current_chaos() is NULL_CHAOS
    assert not NULL_CHAOS.enabled
    NULL_CHAOS.on_engine_step()
    NULL_CHAOS.on_wave(3)
    NULL_CHAOS.maybe_fail_step()
    assert NULL_CHAOS.dead_shards(4) == frozenset()
    assert not NULL_CHAOS.degraded_now()
    assert NULL_CHAOS.queue_pressure() == 0
    assert NULL_CHAOS.take_corruption() is None


def test_use_chaos_restores_previous_controller():
    c = ChaosController([FaultSpec("step_error")])
    with use_chaos(c):
        assert current_chaos() is c
        with use_chaos(None):
            assert current_chaos() is NULL_CHAOS
        assert current_chaos() is c
    assert current_chaos() is NULL_CHAOS
    # and the module-level setter
    set_chaos(c)
    assert current_chaos() is c
    set_chaos(None)
    assert current_chaos() is NULL_CHAOS


# ---- controller clock / arming / budgets ----------------------------------


def test_shard_death_arms_after_clock_and_is_permanent():
    c = ChaosController([FaultSpec("shard_death", shard=1, after=2)])
    assert c.dead_shards(2) == frozenset()
    c.on_engine_step(); c.on_engine_step()
    assert c.dead_shards(2) == frozenset()  # steps == after: not yet
    c.on_engine_step()
    assert c.dead_shards(2) == frozenset({1})
    assert c.degraded_now()
    c.on_engine_step()
    assert c.dead_shards(2) == frozenset({1})  # permanent
    # out-of-topology shard is invisible to a smaller engine
    assert c.dead_shards(1) == frozenset()
    # the death event is announced exactly once
    assert [e["kind"] for e in c.events] == ["shard_death"]


def test_step_error_budget_spends_down():
    c = ChaosController([FaultSpec("step_error", count=2)])
    c.on_engine_step()
    for _ in range(2):
        with pytest.raises(ChaosError):
            c.maybe_fail_step()
    c.maybe_fail_step()  # budget spent: no-op
    assert len(c.events) == 2


# ---- scheduler robustness --------------------------------------------------


def _echo_step(q):
    return q[:, :1] * 0.0, np.zeros((len(q), 1), np.int32)


def test_scheduler_watermark_sheds_at_the_door():
    s = BatchScheduler(_echo_step, batch_size=4, max_queue_rows=6)
    ok = s.submit(np.zeros((4, 8), np.float32))
    shed = s.submit(np.zeros((4, 8), np.float32))
    assert ok.status == "queued" and shed.status == "shed_queue"
    assert shed.shed and shed.result is None
    done = s.drain()
    assert [r.rid for r in done] == [ok.rid] and ok.status == "served"
    assert s.stats["submitted"] == s.stats["served"] + s.stats["shed_queue"]


def test_scheduler_chaos_queue_overload_pressure():
    with use_chaos(parse_chaos("queue_overload:rows=100")):
        current_chaos().on_engine_step()  # arm (after=0 means steps > 0)
        s = BatchScheduler(_echo_step, batch_size=4, max_queue_rows=64)
        r = s.submit(np.zeros((2, 8), np.float32))
    assert r.status == "shed_queue"


def test_scheduler_deadline_shed_before_dispatch():
    s = BatchScheduler(_echo_step, batch_size=4)
    late = s.submit(np.zeros((2, 8), np.float32), deadline_s=-1.0)
    live = s.submit(np.zeros((2, 8), np.float32), deadline_s=60.0)
    done = s.drain()
    assert late.status == "shed_deadline" and late not in done
    assert live.status == "served"
    assert s.stats["shed_deadline"] == 1


def test_scheduler_retry_absorbs_transient_fault():
    with use_chaos(parse_chaos("step_error:count=1")):
        s = BatchScheduler(_echo_step, batch_size=4, max_retries=2,
                           retry_backoff_s=1e-4)
        r = s.submit(np.zeros((2, 8), np.float32))
        s.drain()
    assert r.status == "served"
    assert s.stats["retries"] == 1 and s.stats["shed_error"] == 0


def test_scheduler_retry_exhaustion_sheds_and_serving_continues():
    with use_chaos(parse_chaos("step_error:count=2")):
        s = BatchScheduler(_echo_step, batch_size=4, max_retries=1,
                           retry_backoff_s=1e-4)
        dead = s.submit(np.zeros((2, 8), np.float32))
        s.drain()
        healthy = s.submit(np.zeros((2, 8), np.float32))
        s.drain()
    assert dead.status == "shed_error"
    assert healthy.status == "served"  # one poisoned batch != a dead loop
    assert s.stats["submitted"] == s.stats["served"] + s.stats["shed_error"]


def test_scheduler_tags_degraded_batches():
    with use_chaos(parse_chaos("shard_death:shard=0:after=1")):
        s = BatchScheduler(_echo_step, batch_size=4)
        before = s.submit(np.zeros((4, 8), np.float32))
        s.drain()
        after = s.submit(np.zeros((4, 8), np.float32))
        s.drain()
    assert not before.degraded and after.degraded


# ---- degraded-mode graph search (host-sim failover) ------------------------


@pytest.fixture(scope="module")
def small_graph(aniso_corpus):
    from repro.core import build_estimator
    from repro.index.graph import build_graph

    corpus = np.asarray(aniso_corpus)[:240]
    est = build_estimator("dade", jnp.asarray(corpus), jax.random.PRNGKey(0),
                          delta_d=16)
    gidx = build_graph(corpus, estimator=est, m=8, ef_construction=24,
                       quant="int8")
    return gidx, corpus


def _search(gidx, q, *, shards, tombs=(), **kw):
    from repro.index.graph import search_graph_sharded

    d, i, st = search_graph_sharded(
        gidx, q, num_shards=shards, k=5, ef=16, expand=2, block_q=8,
        tombstones=tombs, **kw)
    return np.asarray(d), np.asarray(i), st


@pytest.mark.parametrize("shards,dead", [(2, (1,)), (3, (0,)), (3, (1, 2))])
def test_failover_matches_surviving_corpus_oracle(small_graph, queries,
                                                  shards, dead):
    from repro.index.graph import dead_shard_tombstones

    gidx, corpus = small_graph
    q = jnp.asarray(np.asarray(queries)[:8, :corpus.shape[1]])
    n = corpus.shape[0]
    tombs = dead_shard_tombstones(n, shards, dead)

    d_deg, i_deg, st = _search(gidx, q, shards=shards, tombs=tombs)
    d_ora, i_ora, _ = _search(gidx, q, shards=1, tombs=tombs, use_ref=True)
    np.testing.assert_array_equal(i_deg, i_ora)
    np.testing.assert_allclose(d_deg, d_ora, rtol=5e-5, atol=1e-5)

    # the degraded run is a real degradation: it differs from healthy
    _, i_ok, _ = _search(gidx, q, shards=shards)
    assert not np.array_equal(i_deg, i_ok)
    # stats carry the failover facts
    assert st.tombstoned_nodes == float(len(dead)) * n / shards
    assert st.dead_shards == tuple(sorted(dead))


def test_failover_dead_entry_falls_back_deterministically(small_graph,
                                                          queries):
    from repro.index.graph import dead_shard_tombstones

    gidx, corpus = small_graph
    n = corpus.shape[0]
    q = jnp.asarray(np.asarray(queries)[:8, :corpus.shape[1]])
    # kill whichever shard owns the builder entry point: the walk must
    # re-seed from the surviving corpus, identically in engine and oracle
    entry_shard = int(np.asarray(gidx.entry)) * 2 // n
    tombs = dead_shard_tombstones(n, 2, (entry_shard,))
    d_deg, i_deg, _ = _search(gidx, q, shards=2, tombs=tombs)
    d_ora, i_ora, _ = _search(gidx, q, shards=1, tombs=tombs, use_ref=True)
    np.testing.assert_array_equal(i_deg, i_ora)
    np.testing.assert_allclose(d_deg, d_ora, rtol=5e-5, atol=1e-5)


def test_failover_rejects_impossible_configs(small_graph, queries):
    gidx, corpus = small_graph
    q = jnp.asarray(np.asarray(queries)[:8, :corpus.shape[1]])
    with pytest.raises(ValueError, match="every node is tombstoned"):
        _search(gidx, q, shards=2, tombs=((0, corpus.shape[0]),))
    from repro.index.graph import dead_shard_tombstones
    with pytest.raises(ValueError):
        dead_shard_tombstones(corpus.shape[0], 2, (5,))  # shard out of range


def test_failover_seed_r_composes_with_tombstones(small_graph, queries):
    # Regression (ISSUE 8): seed_r + tombstones used to be rejected
    # outright.  The threshold seed now samples alive neighbours only, so
    # the composed run must stay bit-identical to the surviving-corpus
    # oracle — and with ``exclude`` (the mutable-index delete semantics,
    # what MutableGraph.search passes) no deleted id may surface.
    gidx, corpus = small_graph
    q = jnp.asarray(np.asarray(queries)[:8, :corpus.shape[1]])
    tombs = ((0, 120),)
    d_deg, i_deg, _ = _search(gidx, q, shards=2, tombs=tombs, seed_r=True,
                              exclude=tombs)
    d_ora, i_ora, _ = _search(gidx, q, shards=1, tombs=tombs, use_ref=True,
                              seed_r=True, exclude=tombs)
    np.testing.assert_array_equal(i_deg, i_ora)
    np.testing.assert_allclose(d_deg, d_ora, rtol=5e-5, atol=1e-5)
    assert not np.any((i_deg >= 0) & (i_deg < 120))


def test_disabled_chaos_is_bit_identical(small_graph, queries):
    # The null-object guarantee: running under an *unarmed* controller (or
    # none) changes nothing about results.
    gidx, corpus = small_graph
    q = jnp.asarray(np.asarray(queries)[:8, :corpus.shape[1]])
    d0, i0, _ = _search(gidx, q, shards=2)
    with use_chaos(ChaosController([FaultSpec("shard_death", shard=1,
                                              after=10**6)])):
        d1, i1, _ = _search(gidx, q, shards=2)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


def test_wave_stall_fires_under_armed_chaos(small_graph, queries):
    gidx, corpus = small_graph
    q = jnp.asarray(np.asarray(queries)[:4, :corpus.shape[1]])
    c = ChaosController([FaultSpec("shard_stall", ms=1.0, count=2)])
    with use_chaos(c):
        c.on_engine_step()  # arm
        _search(gidx, q, shards=2)
    stalls = [e for e in c.events if e["kind"] == "shard_stall"]
    assert len(stalls) == 2  # budget-bounded


# ---- index snapshots (warm restart) ----------------------------------------


def test_graph_index_snapshot_roundtrip(small_graph, queries, tmp_path):
    from repro.checkpoint.index_io import load_graph_index, save_graph_index
    from repro.index.graph import search_graph_beam_host

    gidx, corpus = small_graph
    cfg = {"corpus": corpus.shape[0], "m": 8, "quant": "int8"}
    save_graph_index(str(tmp_path), gidx, config=cfg)
    g2 = load_graph_index(str(tmp_path), expect_config=cfg)
    assert g2 is not None
    assert (g2.adj_block, g2.scan_block_d) == (gidx.adj_block,
                                               gidx.scan_block_d)
    q = jnp.asarray(np.asarray(queries)[:8, :corpus.shape[1]])
    d1, i1, _ = search_graph_beam_host(gidx, q, k=5, ef=16, expand=2,
                                       block_q=8)
    d2, i2, _ = search_graph_beam_host(g2, q, k=5, ef=16, expand=2,
                                       block_q=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2))


def test_graph_index_snapshot_rejects_config_drift(small_graph, tmp_path):
    from repro.checkpoint.index_io import load_graph_index, save_graph_index

    gidx, corpus = small_graph
    save_graph_index(str(tmp_path), gidx, config={"ef_construction": 24})
    assert load_graph_index(str(tmp_path),
                            expect_config={"ef_construction": 64}) is None
    assert load_graph_index(str(tmp_path) + "/nowhere") is None


def test_graph_index_snapshot_tamper_fails_fast(small_graph, tmp_path):
    from repro.checkpoint.index_io import load_graph_index, save_graph_index
    from repro.runtime.chaos import corrupt_checkpoint_leaf

    gidx, _ = small_graph
    save_graph_index(str(tmp_path), gidx, config={})
    corrupt_checkpoint_leaf(os.path.join(str(tmp_path), "step_000000000"),
                            leaf=2)
    with pytest.raises(IOError, match=r"digest mismatch"):
        load_graph_index(str(tmp_path), expect_config={})


def test_estimator_snapshot_roundtrip(small_graph, tmp_path):
    from repro.checkpoint.index_io import load_estimator, save_estimator

    gidx, corpus = small_graph
    est = gidx.estimator
    save_estimator(str(tmp_path), est, config={"v": 1})
    e2 = load_estimator(str(tmp_path), expect_config={"v": 1})
    assert e2 is not None
    assert (e2.method, e2.quant) == (est.method, est.quant)
    x = jnp.asarray(corpus[:4])
    np.testing.assert_allclose(np.asarray(est.rotate(x)),
                               np.asarray(e2.rotate(x)))
    assert load_estimator(str(tmp_path), expect_config={"v": 2}) is None


# ---- the full drill through serve.py (mesh engine, 2 host devices) ---------

_DRILL = textwrap.dedent("""
    import json, subprocess, sys, tempfile, os
    tmp = tempfile.mkdtemp()
    mj = os.path.join(tmp, "m.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--devices", "2", "--index", "graph", "--graph-shards", "2",
         "--corpus-per-device", "600", "--dim", "48", "--requests", "4",
         "--batch", "16", "--ef", "32",
         "--chaos", "shard_death:shard=1:after=2",
         "--verify-degraded-oracle", "--retries", "1",
         "--metrics-json", mj],
        capture_output=True, text=True, env={**os.environ,
                                             "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "verify-degraded: engine with dead shards [1] bit-identical" \\
        in r.stdout, r.stdout
    m = json.load(open(mj))["metrics"]
    v = lambda k: m.get(k, {}).get("value")
    assert v("serve.fault.shard_death") == 1, m
    assert v("graph.sharded.degraded.queries") > 0
    assert v("graph.sharded.degraded.recall_delta") is not None
    assert v("serve.requests.submitted") == v("serve.requests.served") == 4
    print("OK chaos_drill")
""")


@pytest.mark.slow
def test_serve_chaos_drill_end_to_end():
    r = subprocess.run(
        [sys.executable, "-c", _DRILL], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=".", timeout=540)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK chaos_drill" in r.stdout
