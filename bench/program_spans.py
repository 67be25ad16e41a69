"""The program's own host spans in this run's profiler capture.

``tracing.load`` keeps the harness's ``bench.*`` spans only.  The program
writes its spans (``repro.obs.trace``) into any running capture as
``TraceAnnotation``s, on the same clock as the device: the scheduler's are
``sched.pack`` and ``sched.scatter``.  This module finds the capture this
run wrote, reads those spans once and keeps them on the readers' context.
A program that writes no such span (an older one) gives an empty list, and
the readers that need them report nothing.
"""

from __future__ import annotations

import glob
import os

from tracing import Op

TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".cache", "trace")


def _host_events(path):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from line.events


def _read(window):
    """``sched.*`` spans of the newest capture under ``TRACE_ROOT`` whose
    ``bench.window`` span is exactly ``window``, sorted by start."""
    paths = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        found, spans = False, []
        for e in _host_events(path):
            if e.name == "bench.window":
                found = found or (e.start_ns, e.start_ns + e.duration_ns) == window
            elif e.name.startswith("sched."):
                spans.append(Op(e.name, e.start_ns, e.start_ns + e.duration_ns))
        if found:
            lo, hi = window
            return sorted((s for s in spans if s.start >= lo and s.end <= hi),
                          key=lambda s: s.start)
    return []


def spans(ctx, *names):
    """The program's spans named ``names`` inside the traced window."""
    if not hasattr(ctx, "program_spans"):
        ctx.program_spans = _read(tuple(ctx.trace.window))
    return [s for s in ctx.program_spans if s.name in names]

