"""sched.batch_fill: real query rows over dispatched rows, in %, from the
BatchScheduler's own counters over the window."""


def read(ctx):
    batches = ctx.stats.get("batches", 0)
    if not batches:
        return None
    return 100.0 * ctx.stats["rows"] / (batches * ctx.notes["batch"])
