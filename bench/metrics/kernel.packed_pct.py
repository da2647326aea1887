"""kernel.packed_pct: the share of the anneal kernel's device time (every op
matching ``bench/work/anneal.py``'s ``TRACE_NAME``) spent in the variants
that pack two dies into one 128-lane tile, whose ``pallas_call`` names end
in ``_x2`` (``fused_anneal_kernel_x2``, ``fused_anneal_kernel_int8_x2``),
in %. A batch of dies of at most 64 spins reads 100; a program without the
packed kernels reads 0."""
from bench.work import anneal as work

PACKED_NAME = r"^fused_anneal_kernel\w*_x2"


def read(ctx):
    t = ctx.trace
    if t is None or not t.calls:
        return None
    kernel_ns = sum(t.op_ns(work.TRACE_NAME))
    if kernel_ns <= 0:
        return None
    return 100.0 * sum(t.op_ns(PACKED_NAME)) / kernel_ns
