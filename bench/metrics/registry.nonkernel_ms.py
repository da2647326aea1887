"""registry.nonkernel_ms: per call, the benchmark's own span around
``solver.solve`` minus the anneal kernel's device time inside it (LFSR
init, uploads, downloads, host rescoring), averaged over the chips."""
import numpy as np

from bench.work import anneal as work


def read(ctx):
    t = ctx.trace
    if t is None or not t.calls:
        return None
    rest = [(e - s) - float(np.mean(t.op_ns(work.TRACE_NAME, s, e)))
            for s, e in t.calls]
    return float(np.mean(rest)) / 1e6
