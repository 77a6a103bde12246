"""setup_s: seconds from the process's start to the end of the warm-up
requests (JAX start, data, flow build, compiles or cache loads)."""


def read(ctx):
    return ctx.setup_s
