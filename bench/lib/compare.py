"""Shared pieces of the correctness checks: the compared numbers with
their limits, and the exact energy/cut checks every cell applies to every
answer."""
from __future__ import annotations

import dataclasses

import numpy as np

from . import gen


@dataclasses.dataclass
class Verdict:
    attempted: int
    failed: int
    checks: list          # [(name, value, limit)]

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(v <= lim for _, v, lim in self.checks)


def energy_gap(J, energies, sigma) -> float:
    """|reported best energy - float64 energy of the reported spins|."""
    e = np.asarray(energies, dtype=np.float64)
    if e.size == 0 or not np.all(np.isfinite(e)):
        return float("inf")
    s = np.asarray(sigma)
    if s.shape != (J.shape[0],) or not np.all(np.abs(s) == 1):
        return float("inf")
    return float(abs(e.min() - gen.ising_energy_f64(J, s)))
