"""groupby_roofline.<split>: least time the group-by kernels' bytes take
at peak HBM bandwidth (``bench.costs.groupby_bytes``), over the device time
of their programs (``bench.events.PROGRAMS["groupby"]``) in the window."""
from bench import costs


def read(ctx):
    if ctx.trace is None or not ctx.shapes:
        return None
    device_s = ctx.trace.program_ns("groupby") / 1e9
    if device_s <= 0:
        return None
    flow = ctx.cell.flow
    nbytes = sum(costs.groupby_bytes(flow, ctx.shapes[r.fact]["agg_rows"],
                                     ctx.shapes[r.fact]["groups"])
                 for r in ctx.records)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / device_s
