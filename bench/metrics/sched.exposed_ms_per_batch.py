"""sched.exposed_ms_per_batch: time on chip 0, inside the traced window,
in which the BatchScheduler's own host work (a ``sched.pack`` or
``sched.scatter`` span) is open and no operation runs on the device, per
dispatched batch, in ms.  The scheduler's host time that the device waits
for, on the profiler's clock; nothing where the program writes no such
span."""

import program_spans
import tracing


def read(ctx):
    batches = ctx.stats.get("batches", 0)
    spans = program_spans.spans(ctx, "sched.pack", "sched.scatter")
    if not batches or not spans or not ctx.trace.ops:
        return None
    lo, hi = ctx.trace.window
    open_ = tracing.union(((s.start, s.end) for s in spans), lo, hi)
    idle = tracing.idle_gaps(ctx.trace, min(ctx.trace.ops))
    return sum(tracing.covered(open_, s, e) for s, e in idle) / batches / 1e6
