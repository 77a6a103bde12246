"""h2d_share.<split>: share of the window covered by the program's own
host-to-device transfer spans (``repro.obs``, category ``transfer``, name
``h2d``), as the union of their intervals on the host clock."""
from bench import events
from bench.trace import clip, covered


def read(ctx):
    if ctx.spans is None:
        return None
    cat, name = events.H2D_SPAN
    w0, w1 = (t * 1e6 for t in ctx.window)
    spans = [(e["ts"], e["ts"] + e.get("dur", 0.0)) for e in ctx.spans
             if e.get("cat") == cat and e.get("name") == name]
    if not spans:
        return None
    return 100.0 * covered(clip(spans, (w0, w1))) / (w1 - w0)
