"""Operations and bytes the anneal needs, whatever implements it.

One run of the die's anneal is ``n_steps`` Euler steps, each a matrix-
vector product of the n x n couplings with the scaled spin vector:
``2 * n**2 * n_steps`` operations. Only real runs on real spins count: not
the lanes a kernel pads n up to, not the runs it pads up to its run block,
not the idle slots a fabric pads its dies with. A faster kernel changes
the time, never this count, so a share of the roofline cannot pass 100%.

Bytes: each problem's couplings read once at the stated operand width,
each run's voltages read once and written once as float32.

Which instances one call of a cell anneals depends on the deployment: the
configuration's ``work`` names a file beside this one (``die.py``,
``fabric.py``) whose ``call_instances(config, traffic)`` lists them.
"""
from __future__ import annotations

import math

OPERAND_BYTES = {"bfloat16": 2, "float8_e4m3fn": 1, "int8": 1,
                 "float32": 4}


def anneal_ops(n: int, n_steps: int, runs: int) -> float:
    """Operations of ``runs`` anneals of one n-spin instance."""
    return 2.0 * n * n * n_steps * runs


def anneal_bytes(n: int, runs: int, precision: str) -> float:
    """HBM bytes of ``runs`` anneals of one n-spin instance."""
    return n * n * OPERAND_BYTES[precision] + 2.0 * 4 * n * runs


def batch(instances, n_steps: int, precision: str) -> tuple:
    """(ops, bytes) of a list of ``(n_spins, runs)`` anneal instances."""
    ops = sum(anneal_ops(n, n_steps, r) for n, r in instances)
    byt = sum(anneal_bytes(n, r, precision) for n, r in instances)
    return ops, byt


def fabric_instances(n: int, restarts: int, inner_runs: int,
                     die_spins: int, outer_sweeps: int) -> list:
    """The (n_spins, runs) instances one fabric solve anneals: every tile
    of at most ``die_spins - 1`` free spins plus its boundary ancilla, for
    every restart and outer sweep, ``inner_runs`` runs each."""
    k = max(1, math.ceil(n / (die_spins - 1)))
    base, extra = divmod(n, k)
    sizes = [base + 1] * extra + [base] * (k - extra)
    return [(m + 1, restarts * inner_runs * outer_sweeps) for m in sizes]


def least_time(ops: float, byt: float, peak_ops: float,
               peak_bytes_per_s: float) -> tuple:
    """(seconds, 'compute' | 'memory'): the larger of the two bounds."""
    tc, tm = ops / peak_ops, byt / peak_bytes_per_s
    return (tc, "compute") if tc >= tm else (tm, "memory")


#: the fused anneal kernel's operations in a device trace, by the name the
#: trace gives them today (the jitted wrapper's ``fused_anneal_kernel``;
#: the ``pallas_call`` has no name of its own yet)
TRACE_NAME = r"^fused_anneal_kernel"
