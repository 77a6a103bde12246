"""serve_rows_per_s: fact rows of every tick completed in the window, over
the time from the window's start to the end of the last tick."""


def read(ctx):
    if ctx.cell.config["path"] != "serve" or not ctx.records:
        return None
    done = [r for r in ctx.records if r.ok]
    return sum(r.rows for r in done) / (ctx.records[-1].end - ctx.window[0])
