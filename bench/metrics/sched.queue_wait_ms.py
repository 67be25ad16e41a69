"""sched.queue_wait_ms: mean time a query row waited in the
BatchScheduler's queue, from its request's enqueue to the start of its
batch's dispatch, in ms, from the scheduler's own counters (``wait_s``
over ``rows``).  A program without the ``wait_s`` counter reports
nothing."""


def read(ctx):
    rows = ctx.stats.get("rows", 0)
    wait_s = ctx.stats.get("wait_s")
    if not rows or wait_s is None:
        return None
    return 1e3 * wait_s / rows
