"""request_p95_s: 95th percentile of client-side latency over every
request scheduled in the window, each timed from its scheduled send time
to the answer in the client's hands; failed, shed or unanswered requests
rank above every answer."""
import numpy as np


def read(ctx):
    latencies = getattr(ctx.drv, "latencies", None)
    if latencies is None:
        return None
    return float(np.quantile(latencies(ctx.window), 0.95))
