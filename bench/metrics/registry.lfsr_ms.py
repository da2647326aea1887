"""registry.lfsr_ms: host time of the LFSR initial voltages per call, the
summed ``machine.lfsr_init`` spans inside each of the benchmark's
``bench.call`` spans, averaged over the traced calls."""
from bench.lib.spans import mean_inside_ms


def read(ctx):
    t = ctx.trace
    return None if t is None else mean_inside_ms(t, t.calls,
                                                 "machine.lfsr_init")
