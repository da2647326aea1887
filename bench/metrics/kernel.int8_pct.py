"""kernel.int8_pct: the share of the anneal kernel's device time (every op
matching ``bench/work/anneal.py``'s ``TRACE_NAME``) spent in the int8
variant, whose ``pallas_call`` is named ``fused_anneal_kernel_int8``, in
%. A cell on the unit-schedule int8 path reads 100; a change that knocks it
off that path reads less, even where the answers stay bitwise equal."""
from bench.work import anneal as work

INT8_NAME = r"^fused_anneal_kernel_int8"


def read(ctx):
    t = ctx.trace
    if t is None or not t.calls:
        return None
    kernel_ns = sum(t.op_ns(work.TRACE_NAME))
    if kernel_ns <= 0:
        return None
    return 100.0 * sum(t.op_ns(INT8_NAME)) / kernel_ns
