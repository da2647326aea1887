"""Correctness of ``engine`` answers without landscape perturbation, against
the unit-schedule reference (``reference.anneal_unit``).

The comparisons are ``anneal_runs``'s for a closed loop, all exact: every
answer's best energy equals the float64 energy of its spins
(``energy_vs_spins_gap``); on the traffic's ``check_sample``, annealed again
from the same LFSR inits, each run's energy (``runs_unlike_reference``) and
each problem's best spins (``best_spins_unlike_reference``) equal the
reference's. They are made by ``anneal_runs``'s own code, on a private copy
of that module whose reference is the unit-schedule one.
"""
from __future__ import annotations

import types

import numpy as np

from bench.lib import manifest
from bench.reference import anneal_unit


def _unit_run(J, v0, precision):
    if precision not in anneal_unit.EXACT_FOR:
        raise ValueError(f"the unit-schedule reference is exact for "
                         f"{anneal_unit.EXACT_FOR}, not {precision!r}")
    return anneal_unit.run(J, v0)


def check(cell, drv, w, seed: int):
    if not w.calls:
        raise ValueError("anneal_runs_unit checks a closed loop's calls")
    # manifest.code loads a fresh module object: rebinding its reference
    # leaves the ``anneal_runs`` that other cells load untouched
    runs = manifest.code("checks", "anneal_runs")
    runs.anneal = types.SimpleNamespace(run=_unit_run)
    rng = np.random.default_rng([int(seed), 99])
    return runs._closed(cell, drv, w, rng, cell.traffic["check_sample"],
                        cell.config["precision"],
                        int(cell.config["die_spins"]))
