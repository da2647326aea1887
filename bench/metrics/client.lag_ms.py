"""client.lag_ms: 95th percentile of how late the load generator sent a
request against its schedule, so a starved client is not read as a fast
server."""
import math

import numpy as np


def read(ctx):
    lag = [r.sent - r.due for r in ctx.window.requests
           if not math.isnan(r.sent)]
    return float(np.quantile(lag, 0.95) * 1e3) if lag else None
