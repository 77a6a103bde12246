"""probe_efficiency.<split>: probe iterations the rows need, over those the
hash-probe loops run.

Each traced fused-segment call counts, per hash-probe Lookup, a
``("probe", <dim>)`` counter event: ``padded_rows`` the loop ran over (the
chunk's jit bucket), ``passes`` (the loop's trip count, the table's
longest occupied run + 1) and, where the Lookup's key column is a host
input of the call, ``need``: the passes each of the chunk's rows needs to
reach its key or an empty slot, summed (the program walks the table on the
host).  The share is sum(need) / sum(padded_rows * passes) over the
window's events that carry ``need``; None where none does."""

PROBE_CAT = "probe"


def read(ctx):
    if ctx.spans is None:
        return None
    w0, w1 = (t * 1e6 for t in ctx.window)
    need = run = 0
    for e in ctx.spans:
        if e.get("ph") != "C" or e.get("cat") != PROBE_CAT:
            continue
        a = e["args"]
        if not w0 <= e["ts"] <= w1 or "need" not in a:
            continue
        need += a["need"]
        run += a["padded_rows"] * a["passes"]
    if run <= 0:
        return None
    return 100.0 * need / run
