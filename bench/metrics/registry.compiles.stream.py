"""registry.compiles.stream: programs compiled or loaded from the
persistent cache during the window (JAX monitoring events; those of the
lead-in are set-up's and appear in the run's notes). Set-up warms every
flush size, so this should read 0."""


def read(ctx):
    return float(ctx.compiles)
