"""serve.queue_ms: 95th percentile, over the window's answered requests
that were not served from the cache, of how long each queued in the
service before its flush started (``ServeResult.queued_s``)."""
import numpy as np


def read(ctx):
    queued = [getattr(r.result, "queued_s", None)
              for r in ctx.window.requests
              if r.result is not None and not r.result.cached]
    queued = [q for q in queued if q is not None]
    return float(np.quantile(queued, 0.95) * 1e3) if queued else None
