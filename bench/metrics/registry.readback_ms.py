"""registry.readback_ms: host time of the copies of a solve's results to
the host per call (the ``machine.readback`` spans, which start once the
device has finished), summed inside each ``bench.call`` span and averaged
over the traced calls."""
from bench.lib.spans import mean_inside_ms


def read(ctx):
    t = ctx.trace
    return None if t is None else mean_inside_ms(t, t.calls,
                                                 "machine.readback")
