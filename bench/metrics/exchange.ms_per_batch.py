"""exchange.ms_per_batch: time in which a cross-chip collective runs on a
chip, inside the traced window, mean over chips, per dispatched batch, in
ms.

The row-sharded flat step moves two things between chips: the threshold
seed's ``pmin``, an all-reduce that XLA names after the JAX primitive
(``pmin.N``), and the top-K merge's two ``all-gather.N``s.  The reader
matches those instruction names, and any ``-start``/``-done`` halves or
fusions named after a collective; not the program's ``exchange.*``
scopes, which the trace's op events do not carry, so a program without
them reads the same.  A collective that waits for a slower chip counts: a
chip that ends its scan late shows up here.  Nothing on one chip, where
the collectives fold away."""

import re

import tracing

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter"
                        r"|collective-permute|all-to-all|pmin|pmax|psum)\b")


def read(ctx):
    batches = ctx.stats.get("batches", 0)
    if ctx.chips < 2 or not batches or not ctx.trace.ops:
        return None
    lo, hi = ctx.trace.window
    per_chip = [tracing.covered(tracing.union(
        ((o.start, o.end) for o in ops if COLLECTIVE.match(o.name)), lo, hi),
        lo, hi) for ops in ctx.trace.ops.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / batches / 1e6
