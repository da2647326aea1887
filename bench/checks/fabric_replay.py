"""Correctness of ``fabric-jax`` answers against the plain LNS reference.

Every solve of the window: its best energy must equal the float64 energy
of its spins, and the cut read from that energy (cut = sum(W)/4 - H/2)
must equal the exact cut of the spins. One solve drawn from the seed (more
with ``check_sample.calls``) is replayed by ``reference.lns`` from the same
seeds at the configuration's stated precision: every restart's final
energy and the best spins must be equal. The replay does not depend on
the mesh, so on four chips it is also the one-die answer.
"""
from __future__ import annotations

import numpy as np

from bench.lib import gen
from bench.lib.compare import Verdict, energy_gap
from bench.reference import conventions, lns


def check(cell, drv, w, seed: int) -> Verdict:
    rng = np.random.default_rng([int(seed), 99])
    prec = cell.config["precision"]
    die = int(cell.config["die_spins"])
    gap = cut_gap = 0.0
    bad = 0
    for c in w.calls:
        J = drv.pool[c.pool_index][0][0]
        e, s = c.report.energies[0], c.report.best_sigma[0]
        g = energy_gap(J, e, s)
        W = -J.astype(np.float64)
        cg = abs((0.25 * W.sum() - 0.5 * float(np.min(e)))
                 - gen.cut_value(W, s)) if np.isfinite(g) else float("inf")
        gap, cut_gap = max(gap, g), max(cut_gap, cg)
        bad += (g > 0) or (cg > 0)
    picks = rng.choice(len(w.calls), min(len(w.calls),
                                         cell.traffic["check_sample"]
                                         ["calls"]), replace=False)
    off = all_r = best_off = 0
    for k in picks:
        c = w.calls[k]
        J = drv.pool[c.pool_index][0][0]
        sweeps = conventions.fabric_outer_sweeps(
            len(lns.tiles(J.shape[0], die - 1)))
        e_ref, S_ref, _ = lns.solve(
            J, drv.runs, sweeps, c.seed + conventions.FABRIC_SEED_OFFSET,
            inner_runs=int(cell.config["solver_opts"]["inner_runs"]),
            chip_block=die, precision=prec)
        mine = np.asarray(c.report.energies[0], dtype=np.float64)
        n_off = int(np.sum(mine != e_ref))
        b_off = not np.array_equal(np.asarray(c.report.best_sigma[0]),
                                   S_ref[int(np.argmin(e_ref))])
        off += n_off
        all_r += mine.size
        best_off += b_off
        bad += bool(n_off or b_off)
    return Verdict(
        attempted=drv.attempted(w), failed=int(bad),
        checks=[("energy_vs_spins_gap", gap, 0.0),
                ("cut_vs_spins_gap", cut_gap, 0.0),
                ("restarts_unlike_reference", off / max(1, all_r), 0.0),
                ("best_spins_unlike_reference",
                 best_off / max(1, len(picks)), 0.0)])
