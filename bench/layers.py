"""Shared pieces of the per-layer readers in ``bench/metrics/``.

Kernel names: the program's ``pallas_call``s carry no ``name=``.  On the
device trace each kernel is a ``tpu_custom_call`` named after the jitted
function that wraps it (``ivf_scan_kernel_call``), so the readers match
that instruction name.
"""

from __future__ import annotations

import tracing

IVF_SCAN = r"^ivf_scan_kernel_call(\.\d+)?$"


def host_ms_per_step(ctx):
    """Mean over engine steps of (step span wall - device busy inside it),
    on chip 0, in ms; None without steps."""
    steps = ctx.trace.spans_named("bench.engine_step")
    if not steps or not ctx.trace.ops:
        return None
    dev = min(ctx.trace.ops)
    host = [(s.end - s.start) - tracing.busy_ns(ctx.trace, dev, s.start, s.end)
            for s in steps]
    return sum(host) / len(host) / 1e6


def idle_pct(ctx):
    lo, hi = ctx.trace.window
    if not ctx.trace.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - tracing.mean_busy_s(ctx.trace) * 1e9 / (hi - lo))
