"""The reduction from a trace to per-layer metrics, on hand-built traces."""

from types import SimpleNamespace

import pytest

import run
import tracing
from tracing import Op, Trace

MS = 1e6  # trace times are nanoseconds


def make_trace():
    # Window 0-100 ms on two chips.  Chip 0: a 10 ms ivf_scan call inside
    # each of two engine steps, plus one overlapping small op.  Chip 1 is
    # busy 10-30 ms.
    ops = {
        0: [Op("ivf_scan_kernel_call.1", 10 * MS, 20 * MS),
            Op("fusion.1", 15 * MS, 25 * MS),
            Op("ivf_scan_kernel_call.1", 60 * MS, 70 * MS)],
        1: [Op("all-gather.2", 10 * MS, 30 * MS)],
    }
    spans = [Op("bench.window", 0, 100 * MS),
             Op("bench.drain", 5 * MS, 80 * MS),
             Op("bench.engine_step", 8 * MS, 28 * MS),
             Op("bench.engine_step", 50 * MS, 72 * MS),
             Op("bench.wait", 85 * MS, 95 * MS)]
    return Trace(ops=ops, spans=spans, window=(0.0, 100 * MS))


def test_op_names_are_hlo_instruction_names():
    text = ("%ivf_scan_kernel_call.1 = (f32[64,10]{1,0}, s32[64,10]{1,0}) "
            "custom-call(s32[2,256,32]{2,1,0} %broadcast_in_dim.43), "
            'custom_call_target="tpu_custom_call"')
    assert tracing.op_name(text) == "ivf_scan_kernel_call.1"
    assert tracing.op_name("%all-gather.2 = f32[4,64,10] all-gather(x)") == "all-gather.2"


def test_union_and_busy():
    assert tracing.union([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]
    assert tracing.union([(0, 5)], lo=2, hi=4) == [[2, 4]]
    tr = make_trace()
    assert tracing.busy_ns(tr, 0) == pytest.approx(25 * MS)  # 10-25 and 60-70
    assert tracing.mean_busy_s(tr) == pytest.approx((25 + 20) / 2 / 1e3)


def test_idle_gaps_attributed_to_the_open_span():
    tr = make_trace()
    gaps = tracing.idle_gaps(tr, 0)
    assert gaps == [(0, 10 * MS), (25 * MS, 60 * MS), (70 * MS, 100 * MS)]
    assert tracing.innermost_span(tr, 90 * MS) == "bench.wait"
    assert tracing.innermost_span(tr, 40 * MS) == "bench.drain"
    assert tracing.innermost_span(tr, 26 * MS) == "bench.engine_step"
    assert tracing.innermost_span(tr, 2 * MS) == "bench.window"
    b = tracing.breakdown(tr)
    idle = dict(b["idle_gaps"])
    # 0-10: 5 ms outside any span but the window, 5 ms in drain before the
    # step; 25-60 midpoint in drain; 70-100 midpoint (85) at the wait edge.
    assert sum(idle.values()) == pytest.approx(0.075)
    ops = dict(b["device_ops"])
    assert ops["ivf_scan_kernel_call"] == pytest.approx(0.010)  # 20 ms, 2 chips
    assert ops["all-gather"] == pytest.approx(0.010)


def ctx(tr, **kw):
    base = dict(trace=tr, cfg={"route": "flat"}, stats={"batches": 2, "rows": 96},
                answered=96, chips=2, log=lambda m: None,
                notes={"rows_per_chip": 1 << 20, "d_pad": 256, "batch": 64},
                peaks={"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12})
    base.update(kw)
    return SimpleNamespace(**base)


def test_kernel_roofline_and_host_time():
    c = ctx(make_trace())
    roof = run.reader("ivf_scan.roofline")(c)
    least = (1 << 20) * (256 + 4) / 819e9  # bytes bind, not int8 ops
    assert roof == pytest.approx(100 * least / 0.010)
    # Step 1: 20 ms wall, chip 0 busy 10-25 inside 8-28 -> 5 ms host;
    # step 2: 22 ms wall, busy 10 ms -> 12 ms host.
    assert run.reader("flat.host_ms_per_batch")(c) == pytest.approx(8.5)
    assert run.reader("sched.batch_fill")(c) == pytest.approx(75.0)
    assert run.reader("device.idle.bulk")(c) == pytest.approx(100 * (1 - 0.225))


def test_readers_that_find_nothing_return_nothing():
    empty = Trace(ops={}, spans=[Op("bench.window", 0, MS)], window=(0, MS))
    c = ctx(empty, stats={"batches": 0})
    for name in ("ivf_scan.roofline", "flat.host_ms_per_batch",
                 "sched.batch_fill", "device.idle.online"):
        assert run.reader(name)(c) is None
