"""The scheduler's metrics: the program's spans found in this run's capture,
and the two readers on hand-built traces."""

import os
import time
from types import SimpleNamespace

import jax
import pytest

import program_spans
import run
import tracing
from tracing import Op, Trace

MS = 1e6  # trace times are nanoseconds


def ctx(tr, spans=None, **stats):
    c = SimpleNamespace(trace=tr, stats=stats)
    if spans is not None:
        c.program_spans = spans  # what program_spans would read and keep
    return c


def hand_trace():
    # Window 0-100 ms.  Chip 0 runs 10-25 and 60-70, so it idles 0-10,
    # 25-60 and 70-100.
    ops = {0: [Op("ivf_scan_kernel_call.1", 10 * MS, 20 * MS),
               Op("fusion.1", 15 * MS, 25 * MS),
               Op("ivf_scan_kernel_call.1", 60 * MS, 70 * MS)]}
    return Trace(ops=ops, spans=[Op("bench.window", 0, 100 * MS)],
                 window=(0.0, 100 * MS))


def test_exposed_scheduler_time_and_queue_wait():
    spans = [Op("sched.pack", 5 * MS, 12 * MS),      # 5 ms idle (5-10)
             Op("sched.scatter", 22 * MS, 30 * MS),  # 5 ms idle (25-30)
             Op("sched.pack", 40 * MS, 45 * MS),     # 5 ms, all idle
             Op("sched.pack", 42 * MS, 44 * MS),     # inside the last: 0
             Op("sched.scatter", 62 * MS, 68 * MS),  # device busy: 0
             Op("sched.other", 80 * MS, 90 * MS)]    # not read
    c = ctx(hand_trace(), spans, batches=2, rows=96, wait_s=2.4)
    assert run.reader("sched.exposed_ms_per_batch")(c) == pytest.approx(7.5)
    assert run.reader("sched.queue_wait_ms")(c) == pytest.approx(25.0)


def test_scheduler_readers_that_find_nothing_return_nothing():
    spans = [Op("sched.pack", 5 * MS, 12 * MS)]
    exposed = run.reader("sched.exposed_ms_per_batch")
    wait = run.reader("sched.queue_wait_ms")
    # No batches.
    assert exposed(ctx(hand_trace(), spans, batches=0, rows=0, wait_s=0.0)) is None
    assert wait(ctx(hand_trace(), spans, batches=0, rows=0, wait_s=0.0)) is None
    # A program that writes no scheduler span and keeps no wait counter.
    assert exposed(ctx(hand_trace(), [], batches=2, rows=96)) is None
    assert wait(ctx(hand_trace(), [], batches=2, rows=96)) is None
    # No device operations on the trace.
    empty = Trace(ops={}, spans=[Op("bench.window", 0, MS)], window=(0, MS))
    assert exposed(ctx(empty, spans, batches=2, rows=96)) is None


def capture(path, n_pack):
    """A real profiler capture: one bench.window holding ``n_pack``
    sched.pack spans, and one more sched.pack after the window."""
    with jax.profiler.trace(str(path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(n_pack):
                with jax.profiler.TraceAnnotation("sched.pack"):
                    time.sleep(0.001)
        with jax.profiler.TraceAnnotation("sched.pack"):
            time.sleep(0.001)


def test_spans_come_from_the_capture_whose_window_matches(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_ROOT", str(tmp_path))
    capture(tmp_path / "older", 2)
    capture(tmp_path / "newer", 1)
    older = tracing.load(str(tmp_path / "older"))
    newer = tracing.load(str(tmp_path / "newer"))
    (pb,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path / "older")
             for f in fs if f.endswith(".xplane.pb")]
    os.utime(pb, (1, 1))  # older beyond doubt
    c = ctx(older)
    assert len(program_spans.spans(c, "sched.pack")) == 2
    assert c.program_spans is not None  # read once, kept on the context
    assert len(program_spans.spans(ctx(newer), "sched.pack")) == 1
    stray = Trace(ops={}, spans=[], window=(older.window[0], older.window[1] + 1))
    assert program_spans.spans(ctx(stray), "sched.pack") == []
