"""Telemetry subsystem (repro.obs): registry semantics, trace export,
null-tracer zero-cost guarantee, and the serving acceptance property —
span-attached byte counters summing exactly to the engine stats ledgers
with bit-identical results."""

import gc
import json
import sys

import numpy as np
import pytest

from repro.obs import (
    LATENCY_BUCKETS_MS, MetricsRegistry, NULL_TRACER, Tracer, chrome_trace,
    merge_snapshots, span_totals, use_tracer,
)
from repro.obs.metrics import record_graph_sharded
from repro.obs.trace import current_tracer


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_accumulates_and_rejects_negative(self):
        reg = MetricsRegistry()
        reg.counter("a.b").add(2).add(3)
        assert reg.snapshot()["a.b"] == {"type": "counter", "value": 5.0}
        with pytest.raises(ValueError, match="a.b"):
            reg.counter("a.b").add(-1)

    def test_gauge_last_writer_wins(self):
        reg = MetricsRegistry()
        reg.gauge("g").set(1.0)
        reg.gauge("g").set(7.5)
        assert reg.snapshot()["g"]["value"] == 7.5

    def test_histogram_buckets_and_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", bounds=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        snap = reg.snapshot()["lat"]
        assert snap["counts"] == [1, 1, 1, 1]  # one overflow observation
        assert snap["count"] == 4 and snap["sum"] == 555.5
        assert h.percentile(0) <= h.percentile(50) <= h.percentile(100)
        # Overflow observations report the last finite bound (floor).
        assert h.percentile(100) == 100.0

    def test_histogram_rejects_bad_bounds(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="increasing"):
            reg.histogram("h", bounds=(1.0, 1.0))

    def test_name_validation(self):
        reg = MetricsRegistry()
        for bad in ("Upper.case", "tail.", ".head", "sp ace", ""):
            with pytest.raises(ValueError, match="dotted"):
                reg.counter(bad)

    def test_type_collision_fails_fast_naming_key(self):
        reg = MetricsRegistry()
        reg.counter("dco.fetched.bytes")
        with pytest.raises(ValueError, match="dco.fetched.bytes"):
            reg.gauge("dco.fetched.bytes")
        reg.histogram("h", bounds=(1.0, 2.0))
        with pytest.raises(ValueError, match="'h'"):
            reg.histogram("h", bounds=(1.0, 3.0))  # different buckets

    def test_snapshot_deterministic_across_registration_order(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("x").add(1)
        a.gauge("y").set(2)
        b.gauge("y").set(2)
        b.counter("x").add(1)
        assert json.dumps(a.snapshot(), sort_keys=True) == \
            json.dumps(b.snapshot(), sort_keys=True)
        assert list(a.snapshot()) == sorted(a.snapshot())

    def test_merge_semantics(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("c").add(3)
        b.counter("c").add(4)
        a.gauge("g").set(1)
        b.gauge("g").set(9)
        a.histogram("h", bounds=(1.0, 2.0)).observe(0.5)
        b.histogram("h", bounds=(1.0, 2.0)).observe(1.5)
        m = merge_snapshots(a.snapshot(), b.snapshot())
        assert m["c"]["value"] == 7.0  # counters add
        assert m["g"]["value"] == 9.0  # gauges: last writer
        assert m["h"]["counts"] == [1, 1, 0] and m["h"]["count"] == 2
        # Merging must not mutate its inputs (per-shard snapshots get
        # rolled up repeatedly).
        assert a.snapshot()["h"]["counts"] == [1, 0, 0]

    def test_merge_mismatch_fails_naming_key(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("k")
        b.gauge("k")
        with pytest.raises(ValueError, match="'k'"):
            merge_snapshots(a.snapshot(), b.snapshot())
        c, d = MetricsRegistry(), MetricsRegistry()
        c.histogram("hh", bounds=(1.0,))
        d.histogram("hh", bounds=(2.0,))
        with pytest.raises(ValueError, match="'hh'"):
            merge_snapshots(c.snapshot(), d.snapshot())

    def test_default_latency_buckets_are_valid(self):
        assert all(b2 > b1 for b1, b2 in
                   zip(LATENCY_BUCKETS_MS, LATENCY_BUCKETS_MS[1:]))


# ---------------------------------------------------------------------------
# Tracer + Chrome-trace export
# ---------------------------------------------------------------------------


class TestTracer:
    def test_chrome_trace_valid_and_nested(self):
        tr = Tracer(test="nesting")
        with tr.span("outer"):
            with tr.span("inner", x=1):
                tr.instant("tick", bytes=128)
        doc = chrome_trace(tr)
        ev = doc["traceEvents"]
        assert json.loads(json.dumps(doc))  # valid JSON
        assert {e["ph"] for e in ev} == {"X", "i"}
        by = {e["name"]: e for e in ev}
        # Nesting invariant: the child's [ts, ts+dur) interval lies inside
        # the parent's, and depths were recorded innermost-deepest.
        outer, inner = by["outer"], by["inner"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
        assert by["tick"]["args"]["bytes"] == 128
        assert inner["args"] == {"x": 1}
        assert doc["otherData"]["test"] == "nesting"

    def test_span_annotate_and_depth(self):
        tr = Tracer()
        with tr.span("a") as s:
            assert tr.depth() == 1
            s.annotate(k=2)
            tr.annotate(j=3)  # innermost-open-span variant
        assert tr.events[0]["args"] == {"k": 2, "j": 3}
        assert tr.depth() == 0

    def test_use_tracer_restores_previous(self):
        assert current_tracer() is NULL_TRACER
        tr = Tracer()
        with use_tracer(tr):
            assert current_tracer() is tr
            with use_tracer(None):
                assert current_tracer() is NULL_TRACER
            assert current_tracer() is tr
        assert current_tracer() is NULL_TRACER

    def test_null_tracer_zero_allocations_on_step_path(self):
        """The disabled path must allocate nothing: span() returns the
        shared singleton and fence() returns its argument — the zero-cost
        guarantee the engine wave loops rely on."""
        from jax.profiler import TraceAnnotation

        assert not TraceAnnotation.is_enabled()  # no capture running
        t = NULL_TRACER
        payload = object()
        assert t.fence(payload) is payload
        s1 = t.span("wave", wave=0)
        s2 = t.span("other")
        assert s1 is s2  # one process-wide singleton, no per-call objects
        # The hot-loop sequence retains zero allocations: every span is
        # the shared singleton and nothing is recorded.  Interpreter
        # internals drift by a few blocks run-to-run, so the invariant is
        # asserted as NON-SCALING: 10,000 iterations must leave the same
        # constant-noise block delta as zero iterations would — one
        # retained object per span/instant/fence would show as >= 10,000.
        def loop(iters):
            for _ in range(iters):
                with t.span("wave", wave=1):
                    t.instant("tick", bytes=1)
                    t.annotate(x=1)
                    t.fence(payload)

        def delta(iters):
            gc.collect()
            before = sys.getallocatedblocks()
            loop(iters)
            gc.collect()
            return sys.getallocatedblocks() - before

        loop(100)  # warm code objects / caches
        delta(100)
        assert delta(10_000) <= 8

    def test_spans_reach_a_profiler_capture(self, tmp_path):
        """Under a running capture both tracers' spans are host events of
        the written .xplane.pb, on the profiler's clock; the recording
        tracer still keeps its own event for the Chrome export."""
        import glob

        import jax
        from jax.profiler import ProfileData

        from repro.runtime.scheduler import BatchScheduler

        def step(batch):
            return batch[:, :2], np.zeros((len(batch), 2), np.int32)

        sched = BatchScheduler(step, batch_size=4)
        sched.submit(np.ones((6, 3)))
        tr = Tracer()
        with jax.profiler.trace(str(tmp_path)):
            with NULL_TRACER.span("obs.null_span", wave=1):
                pass
            with tr.span("obs.recorded_span", wave=2):
                pass
            sched.drain()
        paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        assert len(paths) == 1
        names = [e.name for plane in ProfileData.from_file(paths[0]).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events]
        for name in ("obs.null_span", "obs.recorded_span"):
            assert names.count(name) == 1, name
        # The scheduler's spans: one pack and one scatter per batch.
        assert sched.stats["batches"] == 2
        assert names.count("sched.pack") == names.count("sched.scatter") == 2
        assert [e["name"] for e in tr.events] == ["obs.recorded_span"]
        assert tr.events[0]["args"] == {"wave": 2}
        assert NULL_TRACER.span("after") is NULL_TRACER.span("capture")

    def test_null_span_without_jax(self, monkeypatch):
        """Where jax cannot be imported no capture can run: span() is the
        shared no-op singleton and nothing raises."""
        from repro.obs import trace

        monkeypatch.setattr(trace, "_annotation_cls", None)
        monkeypatch.setitem(sys.modules, "jax.profiler", None)  # import fails
        s = NULL_TRACER.span("wave", wave=0)
        assert s is NULL_TRACER.span("other")
        assert trace._annotation_cls is trace._NoProfiler
        tr = Tracer()
        with tr.span("recorded"):
            pass
        assert [e["name"] for e in tr.events] == ["recorded"]

    def test_span_totals_aggregates_args(self):
        tr = Tracer()
        with tr.span("w"):
            tr.instant("b", bytes=10)
            tr.instant("b", bytes=32)
        tot = span_totals(tr, arg_keys=("bytes",))
        assert tot["b"]["count"] == 2 and tot["b"]["bytes"] == 42
        assert tot["w"]["count"] == 1 and tot["w"]["total_ms"] >= 0


# ---------------------------------------------------------------------------
# Acceptance: traced sharded graph serving — bit-identity + ledger equality
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def graph_idx(aniso_corpus):
    from repro.index.graph import build_graph
    sub = np.asarray(aniso_corpus)[:1200]
    return sub, build_graph(sub, m=12, ef_construction=48, delta_d=16,
                            quant="int8")


class TestTracedSearchAcceptance:
    def test_sharded_span_bytes_equal_ledgers_and_bit_identity(
            self, graph_idx, queries):
        """The ISSUE-6 acceptance property: per-wave span byte instants
        sum EXACTLY to the GraphShardedStats ledgers (per-shard fetched
        and exchange), and tracing perturbs nothing — results
        bit-identical to the untraced run."""
        import jax.numpy as jnp
        from repro.index.graph import search_graph_sharded

        _, g = graph_idx
        qj = jnp.asarray(queries)
        kw = dict(num_shards=2, k=5, ef=16, block_q=8, use_ref=True)
        d0, i0, st0 = search_graph_sharded(g, qj, **kw)

        tr = Tracer()
        with use_tracer(tr):
            d1, i1, st1 = search_graph_sharded(g, qj, **kw)
        assert np.array_equal(np.asarray(i0), np.asarray(i1))
        assert np.array_equal(np.asarray(d0), np.asarray(d1))
        assert st0 == st1

        qn = len(np.asarray(queries))
        tot = span_totals(tr, arg_keys=("bytes",))
        # Ledger equality, per shard: stage-1 + stage-2 span bytes for
        # shard s == the shard's fetched ledger (seed_r=False default, so
        # no per-query seed term rides the ledger).
        per_shard = {s: 0.0 for s in range(2)}
        for e in tr.events:
            if e["name"] in ("graph.stage1_dma", "graph.stage2"):
                per_shard[e["args"]["shard"]] += e["args"]["bytes"]
        for s in range(2):
            assert per_shard[s] == pytest.approx(
                st1.shard_fetched_bytes_per_query[s] * qn, abs=1e-6)
        assert tot["graph.stage1_dma"]["bytes"] + \
            tot["graph.stage2"]["bytes"] == pytest.approx(
                st1.fetched_bytes_per_query * qn, abs=1e-6)
        assert tot["graph.exchange"]["bytes"] == pytest.approx(
            st1.exchange_bytes_per_query * qn, abs=1e-6)
        # Wave spans: one per executed wave plus the terminal width-0
        # probe; stage spans nest inside.
        assert tot["graph.wave"]["count"] == st1.waves + 1
        assert tot["graph.launch"]["count"] == st1.waves
        assert tot["graph.merge"]["count"] == st1.waves

    def test_graph_search_under_a_profiler_capture(self, graph_idx, queries,
                                                    tmp_path):
        """A graph search under the default tracer while a capture runs:
        the wave loop's ``annotate`` calls reach the captured span, every
        wave shows up as a ``graph.wave`` host event, and the results are
        bit-identical to the search with no capture."""
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        from repro.index.graph import search_graph_fused

        _, g = graph_idx
        qj = jnp.asarray(queries)
        kw = dict(k=5, ef=16, block_q=8, use_ref=True)
        d0, i0, st0 = search_graph_fused(g, qj, **kw)
        assert current_tracer() is NULL_TRACER
        with jax.profiler.trace(str(tmp_path)):
            d1, i1, st1 = search_graph_fused(g, qj, **kw)
        assert np.array_equal(np.asarray(i0), np.asarray(i1))
        assert np.array_equal(np.asarray(d0), np.asarray(d1))
        assert st0 == st1
        paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        assert len(paths) == 1
        names = [e.name for plane in ProfileData.from_file(paths[0]).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events]
        # One wave span per executed wave plus the terminal width-0 probe.
        assert names.count("graph.wave") == st1.waves + 1
        assert names.count("graph.launch") == st1.waves

    def test_wave_spans_nest_stage_spans(self, graph_idx, queries):
        """Chrome-trace nesting: every stage event's interval lies inside
        a wave span's interval (what Perfetto renders as the stack)."""
        import jax.numpy as jnp
        from repro.index.graph import search_graph_sharded

        _, g = graph_idx
        tr = Tracer()
        with use_tracer(tr):
            search_graph_sharded(g, jnp.asarray(queries), num_shards=2,
                                 k=5, ef=16, block_q=8, use_ref=True)
        ev = chrome_trace(tr)["traceEvents"]
        waves = [(e["ts"], e["ts"] + e["dur"]) for e in ev
                 if e["name"] == "graph.wave"]
        stages = [e for e in ev if e["name"] in
                  ("graph.route", "graph.launch", "graph.merge",
                   "graph.host_commit", "graph.stage1_dma", "graph.stage2",
                   "graph.exchange")]
        assert stages, "no stage events recorded"
        eps = 1e-6
        for e in stages:
            end = e["ts"] + e.get("dur", 0.0)
            assert any(lo - eps <= e["ts"] and end <= hi + eps
                       for lo, hi in waves), f"{e['name']} outside waves"

    def test_registry_bridge_matches_ledgers(self, graph_idx, queries):
        import jax.numpy as jnp
        from repro.index.graph import search_graph_sharded

        _, g = graph_idx
        qn = len(np.asarray(queries))
        _, _, st = search_graph_sharded(g, jnp.asarray(queries),
                                        num_shards=2, k=5, ef=16,
                                        block_q=8, use_ref=True)
        reg = MetricsRegistry()
        record_graph_sharded(reg, st, queries=qn)
        snap = reg.snapshot()
        shard_sum = sum(
            snap[k]["value"] for k in snap
            if k.startswith("graph.sharded.shard")
            and k.endswith(".fetched_bytes"))
        assert shard_sum == pytest.approx(snap["dco.fetched.bytes"]["value"])
        assert snap["dco.exchanged.bytes"]["value"] == pytest.approx(
            st.exchange_bytes_per_query * qn)
        assert snap["graph.sharded.waves"]["value"] == st.waves
