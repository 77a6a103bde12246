"""h2d_wait_share.<split>: share of the window covered by the program's
waits for an upload to land on the device (``repro.obs`` spans, category
``wait``, name ``h2d.ready``: ``block_until_ready`` on the fused segment's
packed input, which also waits for the device's queue ahead of it), as the
union of their intervals on the host clock."""
from bench.trace import clip, covered

SPAN = ("wait", "h2d.ready")


def read(ctx):
    if ctx.spans is None:
        return None
    w0, w1 = (t * 1e6 for t in ctx.window)
    spans = [(e["ts"], e["ts"] + e.get("dur", 0.0)) for e in ctx.spans
             if (e.get("cat"), e.get("name")) == SPAN]
    if not spans:
        return None
    return 100.0 * covered(clip(spans, (w0, w1))) / (w1 - w0)
