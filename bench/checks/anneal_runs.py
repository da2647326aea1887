"""Correctness of ``engine`` answers against the plain anneal reference.

Every answer of the window: its best energy must equal the float64 energy
of its spins. A sample drawn from the seed (``check_sample`` in the
traffic file) is annealed again by ``reference.anneal`` from the same LFSR
inits at the configuration's stated precision: each run's energy and each
problem's best spins must be equal, run for run. For a service answer the
inits depend on the request's position in its flush, which the service
does not report, so every position its flush had is tried and the best
match counts (small problems often reach the same energies from several
positions' inits; the served spins must then be one such position's).
"""
from __future__ import annotations

import numpy as np

from bench.lib.compare import Verdict, energy_gap
from bench.reference import anneal, conventions, lfsr


def _padded(J, n_pad):
    out = np.zeros((n_pad, n_pad), dtype=np.float32)
    out[:J.shape[0], :J.shape[0]] = J
    return out


def check(cell, drv, w, seed: int) -> Verdict:
    rng = np.random.default_rng([int(seed), 99])
    sample = cell.traffic["check_sample"]
    prec = cell.config["precision"]
    n_pad = int(cell.config["die_spins"])
    if w.calls:                      # a closed loop's calls
        return _closed(cell, drv, w, rng, sample, prec, n_pad)
    return _service(cell, drv, w, rng, sample, prec, n_pad)


def _closed(cell, drv, w, rng, sample, prec, n_pad) -> Verdict:
    gap, bad = 0.0, 0
    for c in w.calls:
        levels = drv.pool[c.pool_index][0]
        for p, J in enumerate(levels):
            g = energy_gap(J, c.report.energies[p], c.report.best_sigma[p])
            gap = max(gap, g)
            bad += g > 0
    picks = rng.choice(len(w.calls), min(len(w.calls), sample["calls"]),
                       replace=False)
    runs_off = runs_all = best_off = n_best = 0
    for k in picks:
        c = w.calls[k]
        levels = drv.pool[c.pool_index][0]
        probs = np.sort(rng.choice(len(levels), min(len(levels),
                                                    sample["problems"]),
                                   replace=False))
        J = np.stack([_padded(levels[p], n_pad) for p in probs])
        v0 = lfsr.voltages(conventions.engine_seeds(c.seed, probs), n_pad,
                           drv.runs, swing=conventions.ENGINE_SWING)
        sig, e = anneal.run(J, v0, precision=prec)
        for i, p in enumerate(probs):
            mine = np.asarray(c.report.energies[p], dtype=np.float64)
            off = int(np.sum(mine != e[i]))
            n = levels[p].shape[0]
            ref_best = sig[i, int(np.argmin(e[i])), :n]
            best_bad = not np.array_equal(
                np.asarray(c.report.best_sigma[p]), ref_best)
            runs_off += off
            runs_all += mine.size
            best_off += best_bad
            n_best += 1
            bad += bool(off or best_bad)
    return Verdict(
        attempted=drv.attempted(w), failed=int(bad),
        checks=[("energy_vs_spins_gap", gap, 0.0),
                ("runs_unlike_reference", runs_off / max(1, runs_all), 0.0),
                ("best_spins_unlike_reference", best_off / max(1, n_best),
                 0.0)])


def _service(cell, drv, w, rng, sample, prec, n_pad) -> Verdict:
    answered = [r for r in w.requests if r.result is not None]
    unanswered = len(w.requests) - len(answered)
    gap, bad = 0.0, 0
    for r in answered:
        g = energy_gap(drv.levels[r.index], r.result.energies,
                       r.result.sigma)
        gap = max(gap, g)
        bad += g > 0
    k = min(len(answered), int(sample["requests"]))
    picks = set(rng.choice(len(answered), k, replace=False).tolist()) \
        if k else set()
    if answered:   # the largest problem is always among those compared
        picks.add(max(range(len(answered)), key=lambda i: answered[i].n))
    Js, seeds, owner = [], [], []
    for i in sorted(picks):
        r = answered[i]
        for pos in range(max(1, r.result.batch_size)):
            Js.append(_padded(drv.levels[r.index], n_pad))
            seeds.append(conventions.engine_seeds(drv.service_seed,
                                                  [pos])[0])
            owner.append(i)
    worst = 0.0
    if Js:
        v0 = lfsr.voltages(seeds, n_pad, drv.runs,
                           swing=conventions.ENGINE_SWING)
        sig, e = anneal.run(np.stack(Js), v0, precision=prec)
        owner = np.asarray(owner)
        for i in sorted(picks):
            r = answered[i]
            mine = np.asarray(r.result.energies, dtype=np.float64)
            served = np.asarray(r.result.sigma)
            off = 1.0
            for j in np.flatnonzero(owner == i):
                share = float(np.mean(mine != e[j]))
                best = sig[j, int(np.argmin(e[j])), :r.n]
                if not np.array_equal(served, best):
                    share = max(share, 1.0 / mine.size)
                off = min(off, share)
            worst = max(worst, off)
            bad += off > 0
    return Verdict(
        attempted=drv.attempted(w), failed=int(bad + unanswered),
        checks=[("unanswered", float(unanswered), 0.0),
                ("energy_vs_spins_gap", gap, 0.0),
                ("runs_unlike_reference", worst, 0.0)])
