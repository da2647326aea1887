"""serve.flush_size: requests completed per flush over the window, from
the difference of ``IsingService.stats()`` counters across it."""


def read(ctx):
    w = ctx.window
    if not w.stats_after:
        return None
    flushes = w.stats_after["flushes"] - w.stats_before["flushes"]
    done = w.stats_after["completed"] - w.stats_before["completed"]
    return done / flushes if flushes else None
