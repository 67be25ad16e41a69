"""Profiler trace of the measured window, and its reduction.

``capture(dir)`` wraps the window in ``jax.profiler`` with the Python
tracer off.  ``load(dir)`` reads the ``.xplane.pb`` back into a ``Trace``:
per-device lists of operation intervals (the "XLA Ops" line of each
``/device:TPU:<n>`` plane) and the harness's own host spans (every host
event whose name starts with ``bench.``), all in the trace's nanoseconds.
The reductions below are what the per-layer readers share.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
from dataclasses import dataclass, field

import jax

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")


@dataclass
class Op:
    name: str
    start: float
    end: float
    stats: dict = field(default_factory=dict)


@dataclass
class Trace:
    ops: dict  # device index -> list[Op] sorted by start
    spans: list  # host Op with names "bench.*", sorted by start
    window: tuple  # (start, end) of the "bench.window" span

    def __post_init__(self):
        self.span_starts = [s.start for s in self.spans]

    def spans_named(self, name):
        lo, hi = self.window
        return [s for s in self.spans if s.name == name
                and s.start >= lo and s.end <= hi]

    def ops_matching(self, pattern, device=None):
        rx = re.compile(pattern)
        devs = self.ops if device is None else {device: self.ops[device]}
        lo, hi = self.window
        return {d: [o for o in ops if o.end > lo and o.start < hi
                    and rx.search(o.name)]
                for d, ops in devs.items()}


@contextlib.contextmanager
def capture(log_dir: str):
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def op_name(text: str) -> str:
    """The HLO instruction name of a device event, whose name on the TPU
    is the whole instruction text ("%ivf_scan_kernel_call.1 = (...) ...")."""
    return text.split(" = ", 1)[0].lstrip("%")


def _stats(ev) -> dict:
    out = {}
    try:
        for k, v in ev.stats:
            out[str(k)] = v if isinstance(v, (int, float)) else str(v)
    except Exception:  # noqa: BLE001 - stats are optional decoration
        pass
    return out


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        Op(op_name(e.name), e.start_ns,
                           e.start_ns + e.duration_ns, _stats(e))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith("bench."))
    for v in ops.values():
        v.sort(key=lambda o: o.start)
    spans.sort(key=lambda o: o.start)
    win = [s for s in spans if s.name == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    return Trace(ops=ops, spans=spans, window=(win[0].start, win[0].end))


def union(intervals, lo=float("-inf"), hi=float("inf")):
    """Merged, clipped [start, end) intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def busy_ns(trace: Trace, device, lo=None, hi=None) -> float:
    lo = trace.window[0] if lo is None else lo
    hi = trace.window[1] if hi is None else hi
    return covered(union(((o.start, o.end) for o in trace.ops.get(device, [])),
                         lo, hi), lo, hi)


def mean_busy_s(trace: Trace) -> float:
    if not trace.ops:
        return 0.0
    return sum(busy_ns(trace, d) for d in trace.ops) / len(trace.ops) / 1e9


def idle_gaps(trace: Trace, device):
    lo, hi = trace.window
    merged = union(((o.start, o.end) for o in trace.ops.get(device, [])), lo, hi)
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    return [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]


def innermost_span(trace: Trace, t: float) -> str:
    """Name of the latest-started harness span still open at ``t`` (spans
    of one thread nest, so that is the innermost)."""
    starts = trace.span_starts
    j = bisect.bisect_right(starts, t) - 1
    while j >= 0:
        s = trace.spans[j]
        if s.end >= t and s.name != "bench.window":
            return s.name
        j -= 1
    return "bench.window"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Device operations that took most time (seconds, mean over chips),
    and idle time on chip 0 by the innermost harness span open in it."""
    lo, hi = trace.window
    tot = {}
    for ops in trace.ops.values():
        for o in ops:
            if o.end > lo and o.start < hi:
                key = re.sub(r"\.\d+$", "", o.name)
                tot[key] = tot.get(key, 0.0) + (min(o.end, hi) - max(o.start, lo))
    n = max(len(trace.ops), 1)
    device_ops = sorted(([k, v / n / 1e9] for k, v in tot.items()),
                        key=lambda kv: -kv[1])[:top]
    by_span = {}
    dev0 = min(trace.ops) if trace.ops else None
    for s, e in (idle_gaps(trace, dev0) if dev0 is not None else []):
        name = innermost_span(trace, (s + e) / 2)
        by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9
    idle = sorted(([k, v] for k, v in by_span.items()), key=lambda kv: -kv[1])[:top]
    return {"device_ops": device_ops, "idle_gaps": idle}
