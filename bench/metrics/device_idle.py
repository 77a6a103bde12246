"""device_idle.<split>: share of the traced window in which no operation
ran on the device (1 - busy / window, averaged over the chips used)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_ns / ctx.trace.window_ns)
