"""window_compiles.<split>: XLA programs that jit's in-memory cache missed
inside the measured window, compiled or loaded from the persistent cache
(``jax.monitoring``); a warm-up that sent every input leaves none."""


def read(ctx):
    return float(ctx.window_compiles)
