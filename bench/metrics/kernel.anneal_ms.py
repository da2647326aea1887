"""kernel.anneal_ms: device time of the fused anneal kernel per call (one
chip64 batch, or one whole fabric solve), summed over its events in the
trace and averaged over the chips used."""
import numpy as np

from bench.work import anneal as work


def read(ctx):
    t = ctx.trace
    if t is None or not t.calls:
        return None
    per_chip = float(np.mean(t.op_ns(work.TRACE_NAME)))
    return per_chip / len(t.calls) / 1e6 if per_chip else None
