"""serve.validate_ms: host time of the service's float64 validation of a
flush's answers, the ``serve.validate`` spans summed inside each
``serve.flush`` span of the traced window and averaged over the flushes."""
from bench.lib.spans import mean_inside_ms, named


def read(ctx):
    t = ctx.trace
    return None if t is None else mean_inside_ms(
        t, named(t, "serve.flush"), "serve.validate")
