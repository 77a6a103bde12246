"""sum_limbs.<split>: 8-bit limb columns the exact group-by sums for each
distinct integer sum input, on average.

Each traced group-by call that sums integer inputs exactly emits an
``("exact", "groupby")`` counter event: ``rows``, ``columns`` (distinct
integer inputs), ``limbs`` (limb columns summed: each input less its
minimum, in as many 8-bit limbs as its span needs) and ``max_bits``.  The
reading is sum(limbs) / sum(columns) over the window's events; None where
there is none (a program without exact sums)."""

CAT, NAME = "exact", "groupby"


def read(ctx):
    if ctx.spans is None:
        return None
    w0, w1 = (t * 1e6 for t in ctx.window)
    limbs = columns = 0
    for e in ctx.spans:
        if (e.get("ph"), e.get("cat"), e.get("name")) != ("C", CAT, NAME):
            continue
        if not w0 <= e["ts"] <= w1:
            continue
        limbs += e["args"]["limbs"]
        columns += e["args"]["columns"]
    if columns <= 0:
        return None
    return limbs / columns
