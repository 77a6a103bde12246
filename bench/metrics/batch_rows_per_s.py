"""batch_rows_per_s: fact rows of every batch run in the window, over the
time from the window's start to the end of the last run."""


def read(ctx):
    if ctx.cell.config["path"] != "batch" or not ctx.records:
        return None
    done = [r for r in ctx.records if r.ok]
    return sum(r.rows for r in done) / (ctx.records[-1].end - ctx.window[0])
