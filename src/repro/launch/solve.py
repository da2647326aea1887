"""Ising-solve driver — the paper's workload as a production service.

    PYTHONPATH=src python -m repro.launch.solve --solver engine \
        --spins 64 --density 0.5 --problems 4 --runs 256

    # 128-spin Max-Cut on the multi-chip decomposition solver
    PYTHONPATH=src python -m repro.launch.solve --solver chip-lns \
        --workload maxcut --spins 128 --problems 1 --runs 16

    # 2000-spin Gset Max-Cut on the mesh-sharded mega-fabric (8 emulated
    # dies; prints the per-color dispatch/occupancy ledger; gset graph
    # sparsity is set by --degree, default 6 — not --density)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.solve --solver fabric-jax \
        --workload gset --spins 2000 --problems 1 --runs 4 \
        --mesh-devices 8 --no-oracle

    # NP-hard zoo: coloring / mis / vertex-cover / 3sat / tsp
    PYTHONPATH=src python -m repro.launch.solve --solver tabu \
        --workload mis --spins 12 --runs 32

    # the classical search tier at machine batch scale: tabu-jax is the
    # best-known oracle vmapped over restarts x problems (one dispatch per
    # pad bucket), pt-jax is replica-exchange parallel tempering
    PYTHONPATH=src python -m repro.launch.solve --solver tabu-jax \
        --spins 48 --problems 8 --runs 64

    # analog device-physics tier: a 256-virtual-chip robustness sweep
    # (per-chip coupling mismatch + leakage spread) in one dispatch
    PYTHONPATH=src python -m repro.launch.solve --solver ode-jax \
        --spins 64 --problems 2 --runs 8 --chips 256 \
        --mismatch-sigma 0.1 --tau-leak-spread 0.3

Any registered solver (``--list-solvers``) runs behind the same
Problem/Suite/Report surface; the best-known oracle is disk-cached by
problem content hash (``--no-cache`` bypasses) and refreshed by the
batched on-device tabu-jax tier above the brute-force range. Single-die
solvers declare ``max_n`` and reject suites past one 64-spin block —
``chip-lns`` decomposes larger instances onto the same engine. Zoo
workloads decode the best configuration back to native form and verify it
(``repro.workloads``).
"""
from __future__ import annotations

import argparse

from ..api import ProblemSuite, get_solver, list_solvers, solve_suite
from ..utils import enable_compile_cache

#: --workload values that are plain Problem constructors, not zoo entries.
_BUILTIN = ("random-qubo", "maxcut", "gset")


def build_suite(workload: str, n: int, density: float, problems: int,
                seed: int, degree: float | None = None) -> ProblemSuite:
    """One suite for any workload name: built-ins keep the paper's problem
    families; everything else resolves through the ``repro.workloads``
    registry (``n`` is the native size — nodes / variables / cities).
    ``--density`` reaches every generator that takes one (the graph
    workloads); 3sat/tsp have their own shape knobs and ignore it. The
    ``gset`` family is parameterized by expected vertex ``degree``
    instead (G1-class graphs are ~degree-6 at every N, not a fixed edge
    fraction) — ``--density`` does not apply to it."""
    import inspect

    from ..api import Problem
    if workload == "random-qubo":
        return ProblemSuite.random(n, density, problems, seed=seed)
    if workload == "maxcut":
        return ProblemSuite([Problem.maxcut(n, density, seed=seed + i)
                             for i in range(problems)])
    if workload == "gset":
        from ..problems.gset import gset_problem
        deg = 6.0 if degree is None else float(degree)
        return ProblemSuite([gset_problem(n, seed=seed + i, degree=deg)
                             for i in range(problems)])
    from ..workloads import get_workload
    gen = get_workload(workload).random_instance
    kw = {"density": density} \
        if "density" in inspect.signature(gen).parameters else {}
    return ProblemSuite.workload(workload, size=n, num_problems=problems,
                                 seed=seed, **kw)


def solve(n_spins: int, density: float, problems: int, runs: int,
          seed: int = 0, solver: str = "engine", backend: str = "auto",
          perturbation: bool = True, autotune: bool = False,
          budget: float | None = None, use_cache: bool = True,
          workload: str = "random-qubo", chips: int = 1,
          mismatch_sigma: float = 0.0, tau_leak_spread: float = 0.0,
          mesh_devices: int | None = None, oracle: bool = True,
          degree: float | None = None):
    """Solve one workload cell through the registry; returns
    ``(report, suite)`` — the oracle-attached
    :class:`repro.api.SolveReport` plus the suite it solved (callers need
    the problems to decode zoo solutions back to native form)."""
    suite = build_suite(workload, n_spins, density, problems, seed,
                        degree=degree)
    opts = {}
    if solver == "engine":
        opts = dict(backend=backend, autotune=autotune,
                    variant="perturbation" if perturbation else "gd")
    elif solver == "chip-lns":
        opts = dict(backend=backend)
    elif solver == "fabric-jax":
        opts = dict(backend=backend, mesh_devices=mesh_devices)
    elif solver == "ode-jax":
        from ..physics import VariationModel
        opts = dict(variant="perturbation" if perturbation else "gd",
                    n_chips=chips,
                    variation=VariationModel(
                        j_mismatch_sigma=mismatch_sigma,
                        tau_leak_spread=tau_leak_spread))
    return solve_suite(suite, solver=solver, runs=runs, seed=seed + 1,
                       budget=budget, use_cache=use_cache, oracle=oracle,
                       **opts), suite


def _print_native(workload: str, suite: ProblemSuite, report) -> None:
    """Decode + verify each best configuration back in native terms."""
    from ..workloads import get_workload
    wl = get_workload(workload)
    for i, p in enumerate(suite):
        res = wl.verify(p, wl.decode(p, report.best_sigma[i]))
        print(f"[{workload} #{i}] feasible={res.feasible} "
              f"objective={res.objective:g} ({wl.sense})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--solver", default="engine",
                    help="registered solver name (see --list-solvers)")
    ap.add_argument("--list-solvers", action="store_true",
                    help="print the solver registry and exit")
    ap.add_argument("--workload", default="random-qubo",
                    help="problem family: random-qubo, maxcut, or any "
                         "registered zoo workload (coloring, mis, "
                         "vertex-cover, 3sat, tsp)")
    ap.add_argument("--spins", type=int, default=64,
                    help="native size: spins for random-qubo/maxcut, "
                         "nodes/variables/cities for zoo workloads")
    ap.add_argument("--density", type=float, default=0.5,
                    help="edge/coupling density for random-qubo, maxcut "
                         "and density-taking zoo workloads (not gset — "
                         "see --degree)")
    ap.add_argument("--degree", type=float, default=None,
                    help="[gset] expected vertex degree of the sparse "
                         "Max-Cut graph (default 6.0, the G1-class "
                         "regime); gset ignores --density")
    ap.add_argument("--problems", type=int, default=4)
    ap.add_argument("--runs", type=int, default=256)
    ap.add_argument("--budget", type=float, default=None,
                    help="effort multiplier, mapped uniformly by "
                         "api.budget.search_effort: scales per-restart "
                         "iterations (anneal length for engine, outer "
                         "sweeps for chip-lns, sweeps for SA/PT, flips "
                         "for tabu), never the restart count")
    ap.add_argument("--backend", choices=["jnp", "pallas", "auto"],
                    default="auto",
                    help="[engine/chip-lns] AnnealEngine path: jnp=scan, "
                         "pallas=fused, auto=engine decides")
    ap.add_argument("--no-perturbation", action="store_true",
                    help="[engine] gradient-descent baseline variant")
    ap.add_argument("--autotune", action="store_true",
                    help="[engine] benchmark block_r/path candidates for "
                         "this workload and persist the winner")
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the disk-backed best-known oracle cache")
    ap.add_argument("--no-oracle", action="store_true",
                    help="skip the best-known oracle entirely (success "
                         "metrics unavailable) — the only sane setting at "
                         "Gset scale, where the tabu refresh would dwarf "
                         "the solve")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="[fabric-jax] dies in the fabric mesh (default: "
                         "all visible devices; set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=K before "
                         "launch to emulate a K-die fabric on one host)")
    ap.add_argument("--chips", type=int, default=1,
                    help="[ode-jax] virtual-chip fleet size: every chip "
                         "gets its own seeded variation draw and all "
                         "chips x runs ride ONE dispatch per pad bucket")
    ap.add_argument("--mismatch-sigma", type=float, default=0.0,
                    help="[ode-jax] per-cell multiplicative coupling "
                         "mismatch sigma (J_eff = J * (1 + sigma*z))")
    ap.add_argument("--tau-leak-spread", type=float, default=0.0,
                    help="[ode-jax] lognormal spread of the gate-leak "
                         "time constant across chips")
    args = ap.parse_args()
    enable_compile_cache()

    if args.list_solvers:
        for name, caps in list_solvers().items():
            lim = f" N<={caps.max_n}" if caps.max_n else ""
            print(f"{name:12s} device={caps.device:5s} "
                  f"exact={caps.exact} needs_oracle={caps.needs_oracle}{lim}")
        return

    get_solver(args.solver)     # fail fast on unknown names
    report, suite = solve(
        args.spins, args.density, args.problems, args.runs,
        solver=args.solver, backend=args.backend,
        perturbation=not args.no_perturbation, autotune=args.autotune,
        budget=args.budget, use_cache=not args.no_cache,
        workload=args.workload, chips=args.chips,
        mismatch_sigma=args.mismatch_sigma,
        tau_leak_spread=args.tau_leak_spread,
        mesh_devices=args.mesh_devices, oracle=not args.no_oracle,
        degree=args.degree)
    plan = report.meta.get("engine_plan")
    if plan:
        print(f"[engine] path={plan['path']} block_r={plan['block_r']} "
              f"j_dtype={plan['j_dtype']} interpret={plan['interpret']} "
              f"({plan['reason']})")
    fab = report.meta.get("fabric")
    if fab:
        print(f"[fabric] {fab['mesh_devices']} dies, "
              f"{fab['n_colors']} colors x "
              f"{report.meta['outer_sweeps']} sweeps = "
              f"{fab['dispatches']} dispatches, "
              f"{fab['field_exchanges']} field exchanges")
        for occ in fab["occupancy"]:
            per_p = [f"p{k[1:]}:{v['tiles']}t/" f"{v['dies_busy']}d"
                     f"(+{v['pad_tiles']}pad)"
                     for k, v in occ.items() if k != "color"]
            print(f"[fabric]   color {occ['color']}: peak "
                  f"{fab['color_peaks'][occ['color']]} tiles/die — "
                  + " ".join(per_p))
    print(report.summary())
    if args.workload not in _BUILTIN:
        _print_native(args.workload, suite, report)
    elif args.workload in ("maxcut", "gset"):
        from ..core.hamiltonian import maxcut_value
        for i, p in enumerate(suite):
            cut = float(maxcut_value(p.meta["W"], report.best_sigma[i]))
            print(f"[{args.workload} #{i}] N={p.n} cut weight={cut:g}")


if __name__ == "__main__":
    main()
