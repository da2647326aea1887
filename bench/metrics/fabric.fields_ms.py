"""fabric.fields_ms: mean time of the boundary-field exchange per outer
sweep (the fabric ledger's ``t_fields``: the sharded contraction, its
psum across chips, and the copy back to the host)."""
from bench.lib.ledger import fabric_mean_ms


def read(ctx):
    return fabric_mean_ms(ctx.window, "t_fields")
