"""fabric.dispatch_ms: what a colour-phase engine dispatch of the fabric
costs beyond its kernel: per ``fabric.engine`` span (uploads, the engine's
plan and dispatch, the copies back), its length minus the anneal kernel's
device time inside it, averaged over the chips, then over the spans."""
import numpy as np

from bench.lib.spans import named
from bench.work import anneal as work


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    phases = named(t, "fabric.engine")
    if not phases:
        return None
    rest = [(e - s) - float(np.mean(t.op_ns(work.TRACE_NAME, s, e)))
            for s, e in phases]
    return float(np.mean(rest)) / 1e6
