"""Solver-matrix smoke: every registered solver on one shared mixed-size
suite, through the registry. Produces per-solver anneals/s + success rate
in ``experiments/bench/solver_matrix.json`` AND ``BENCH_solvers.json`` at
the repo root (next to BENCH_kernel.json) so CI archives the solver-level
perf trajectory from every run.

Solvers whose caps can't take the whole suite (brute-force: N <= 24,
engine: one 64-spin die) are scored on the subset they support (noted in
the payload). The suite mixes the paper's random-QUBO grid with two
encoded zoo workloads (MIS + graph coloring, ``repro.workloads``) so every
solver is exercised on structured penalty landscapes, not just random
couplings — the encodings ride the same ``Problem`` surface for free.

Three gates make this a CI check, not just a report:

  * every ``device="jax"`` solver must take at most one dispatch per pad
    bucket of its suite — a batched solver quietly regressing to
    per-problem dispatch fails the run;
  * jax solvers run with ``warmup=True``, so ``anneals_per_s`` measures
    steady-state throughput and one-time XLA compilation lands in the
    separate ``compile_s`` column;
  * ``sb-jax`` (simulated bifurcation, the state-of-the-art classical
    competitor on dense Max-Cut) must reach SR >= the engine's
    perturbation baseline on the dense Max-Cut slice — the frontier row
    the solver exists to claim (``success_rate_maxcut`` per solver).
"""
from __future__ import annotations

import time

from repro.api import (Problem, ProblemSuite, best_known_energies,
                       get_solver, list_solvers)

from .common import csv_line, record, write_root_bench


def run(full: bool = False):
    t0 = time.time()
    sizes = (16, 32, 64) if full else (16, 32)
    per_size, runs = (4, 256) if full else (2, 32)
    n_cut, per_cut = (48, 4) if full else (24, 3)
    suite = ProblemSuite.grid(sizes=sizes, densities=(0.5,),
                              problems_per_cell=per_size, seed=515)
    suite = suite + ProblemSuite.workload("mis", size=10, seed=515) \
        + ProblemSuite.workload("coloring", size=5, seed=515)
    # Dense Max-Cut slice: the workload class SB claims state-of-the-art
    # on. Kept within one 64-spin die so the engine rows cover it too —
    # the sb-jax >= engine SR gate below reads exactly this slice.
    maxcut = ProblemSuite([Problem.maxcut(n_cut, density=0.9, seed=606 + i)
                           for i in range(per_cut)])
    suite = suite + maxcut
    maxcut_hashes = frozenset(maxcut.hashes)
    bk = best_known_energies(suite, seed=2)

    results = {}
    for name, caps in list_solvers().items():
        sub, sub_bk = suite, bk
        if caps.max_n is not None:
            keep = [i for i, n in enumerate(suite.sizes) if n <= caps.max_n]
            sub = ProblemSuite([suite[i] for i in keep])
            sub_bk = bk[keep]
        try:
            solver = (get_solver(name, warmup=True) if caps.device == "jax"
                      else get_solver(name))
        except TypeError:       # user-registered solver without warmup kwarg
            solver = get_solver(name)
        rep = solver.solve(sub, runs=runs, seed=11)
        if caps.device == "jax" and rep.dispatches > sub.num_dispatches():
            raise RuntimeError(
                f"batched solver {name!r} issued {rep.dispatches} dispatches "
                f"for a {sub.num_dispatches()}-bucket suite — the one-"
                f"dispatch-per-bucket hot path regressed")
        rep.attach_oracle(rep.best_energy if caps.exact else sub_bk)
        m = rep.metrics()
        sr_all = rep.success_rate()
        cut_idx = [i for i, h in enumerate(sub.hashes)
                   if h in maxcut_hashes]
        sr_cut = (float(sr_all[cut_idx].mean()) if cut_idx else None)
        results[name] = {
            "anneals_per_s": float(rep.anneals_per_s),
            "success_rate": float(m["mean_success_rate"]),
            "success_rate_maxcut": sr_cut,
            "wall_s": float(rep.wall_s),
            "compile_s": float(rep.compile_s),
            "dispatches": int(rep.dispatches),
            "num_problems": rep.num_problems,
            "runs": int(rep.runs),
            "device": caps.device,
            "subset_max_n": caps.max_n,
        }

    # -- N=128 decomposition row: chip-lns vs fabric-jax ------------------
    # The first N > 64 line in the perf trajectory: both decomposition
    # settings of the one LNS on one 128-spin instance at identical
    # seeds/effort. chip-lns (one color, one die) is one dispatch per
    # sweep; fabric-jax one dispatch per color phase — the ledger shapes
    # are pinned here, the wall/energy columns track the trajectory.
    import numpy as np
    from repro.distributed.fabric import lns_blocks
    p128 = Problem.maxcut(128, density=0.5, seed=717)
    lns_runs, lns_outer = (8, 8) if full else (4, 4)
    dec = {}
    for name in ("chip-lns", "fabric-jax"):
        solver = get_solver(name, anneal_sweeps=0.5, inner_runs=4,
                            outer_sweeps=lns_outer)
        rep = solver.solve(p128, runs=lns_runs, seed=11)
        dec[name] = {"best_energy": float(np.min(rep.energies[0])),
                     "wall_s": float(rep.wall_s),
                     "dispatches": int(rep.dispatches)}
    n_tiles = len(lns_blocks(128, 63))
    if dec["chip-lns"]["dispatches"] != lns_outer:
        raise RuntimeError(
            f"chip-lns issued {dec['chip-lns']['dispatches']} dispatches "
            f"for {lns_outer} outer sweeps — the one-dispatch-per-sweep "
            f"stacking regressed")
    if dec["fabric-jax"]["dispatches"] != 2 * lns_outer:
        raise RuntimeError(
            f"fabric-jax issued {dec['fabric-jax']['dispatches']} "
            f"dispatches for 2 colors x {lns_outer} sweeps ({n_tiles} "
            f"tiles) — the per-color-phase ledger regressed")

    sb_cut = results["sb-jax"]["success_rate_maxcut"]
    engine_cut = results["engine"]["success_rate_maxcut"]
    if sb_cut is None or engine_cut is None or sb_cut < engine_cut:
        raise RuntimeError(
            f"sb-jax must match or beat the engine's perturbation baseline "
            f"on the dense Max-Cut slice: SR {sb_cut} vs engine "
            f"{engine_cut} — the SB frontier row regressed")

    payload = {"sizes": list(sizes), "per_size": per_size, "runs": runs,
               "maxcut_slice": {"n": n_cut, "density": 0.9,
                                "problems": per_cut},
               "suite_dispatch_buckets": suite.num_dispatches(),
               "solvers": results,
               "decomposition_128": {"n": 128, "runs": lns_runs,
                                     "outer_sweeps": lns_outer, **dec},
               "wall_time": time.strftime("%Y-%m-%d %H:%M:%S")}
    record("solver_matrix", payload)
    write_root_bench("BENCH_solvers.json", payload)

    us = (time.time() - t0) * 1e6 / max(len(suite) * runs, 1)
    derived = ";".join(f"{k}={v['anneals_per_s']:.0f}/s,sr={v['success_rate']:.2f}"
                       for k, v in results.items())
    print(csv_line("solver_matrix", us, derived))
    return payload


if __name__ == "__main__":
    run()
