"""segment_roofline.<split>: least time the fused segment's bytes take at
peak HBM bandwidth (``bench.costs.segment_bytes``), over the device time of
the segment program (``bench.events.PROGRAMS["segment"]``) in the window."""
from bench import costs


def read(ctx):
    if ctx.trace is None:
        return None
    device_s = ctx.trace.program_ns("segment") / 1e9
    if device_s <= 0:
        return None
    flow = ctx.cell.flow
    nbytes = sum(costs.segment_bytes(flow, r.rows, ctx.dim_rows)
                 for r in ctx.records)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / device_s
