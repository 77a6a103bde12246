"""serve_tick_p95_ms: the 95th percentile of every tick's time in the
window, from ``tick()`` to its delta on the host (host clock).  A tick that
failed counts as missing every limit: it reads as ``check.UNCOMPARABLE``."""
import numpy as np

from bench.check import UNCOMPARABLE


def read(ctx):
    if ctx.cell.config["path"] != "serve" or not ctx.records:
        return None
    times = [(r.end - r.start) * 1e3 if r.ok else UNCOMPARABLE
             for r in ctx.records]
    return float(np.percentile(np.asarray(times), 95,
                               method="inverted_cdf"))
