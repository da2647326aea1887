"""fabric.accept_ms: mean host time of the exact float64 acceptance per
outer sweep (the fabric ledger's ``t_accept``), over the window's solves."""
from bench.lib.ledger import fabric_mean_ms


def read(ctx):
    return fabric_mean_ms(ctx.window, "t_accept")
