"""ivf_scan.roofline: least time of a flat batch step's stage-1 scan over
the measured time of the ivf_scan kernel, in %, mean over chips."""

import costs
import layers


def read(ctx):
    ops = ctx.trace.ops_matching(layers.IVF_SCAN)
    calls = sum(len(v) for v in ops.values())
    spent = sum(o.end - o.start for v in ops.values() for o in v) / 1e9
    if not calls or spent <= 0:
        return None
    n = ctx.notes
    least, bound = costs.least_time_s(
        costs.ivf_scan(n["rows_per_chip"], n["d_pad"], n["batch"]), ctx.peaks)
    ctx.log(f"ivf_scan.roofline: {calls} calls, {spent / calls * 1e3:.4f} ms "
            f"per call, least {least * 1e3:.4f} ms bound by {bound}")
    return 100.0 * least * calls / spent
