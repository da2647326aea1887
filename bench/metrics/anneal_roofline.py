"""anneal_roofline: the anneal kernel's share of its roofline, in %.

Least time is the larger of the useful operations over the chips' peak at
the configuration's stated precision and the useful bytes over their HBM
bandwidth (``bench/work/anneal.py``: real runs on real spins only); the
share is least time over the kernel's device time, summed over chips.
``notes`` in the result line says which bound it is."""
from bench.lib import manifest
from bench.work import anneal as work


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peaks is None or not t.calls:
        return None
    kernel_s = sum(t.op_ns(work.TRACE_NAME)) * 1e-9
    if kernel_s <= 0:
        return None
    cfg = ctx.cell.config
    calls = manifest.code("work", cfg["work"])
    inst = calls.call_instances(cfg, ctx.cell.traffic) * len(t.calls)
    ops, byt = work.batch(inst, int(cfg["n_steps"]), cfg["precision"])
    least, bound = work.least_time(ops, byt,
                                   ctx.peaks["ops_per_s"][cfg["precision"]],
                                   ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["anneal_roofline"] = f"{bound}-bound"
    return 100.0 * least / kernel_s
