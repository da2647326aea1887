"""problems_per_s: answers completed in the window over its length.

The window runs whole calls: it starts at the first timed call and ends
when the first call to finish after ``--seconds`` completes."""


def read(ctx):
    rate = getattr(ctx.drv, "problems_per_s", None)
    return None if rate is None else rate(ctx.window)
