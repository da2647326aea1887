"""Chip smoke: the Ising system's main path, once, on one TPU.

    python3 chip_smoke.py                # one chip: every phase below
    python3 chip_smoke.py --four-chips   # four chips: the sharded fabric only

Phases (one process, so one owner of the chip):

* engine — the paper's ``chip64`` protocol (256 problems x 1024 LFSR runs
  x 64 spins, random QUBO at density 0.5) through ``launch.solve.solve``,
  with landscape perturbation and with the gradient-descent baseline. The
  plan must be the compiled fused kernel (``path=fused``,
  ``interpret=False``, ``reason=auto``). Checks: the baseline's per-run
  spins and energies on an 8-problem x 128-run slice equal the scan path
  on this host's CPU bitwise (unit schedule + int8 couplings is
  integer-exact); every reported energy equals the float64 energy of its
  spins; SR(perturbation) >= SR(baseline) against the shared oracle (the
  paper's claim). Fused-vs-scan agreement under perturbation is printed,
  not gated.
* fabric — one ``fabric-jax`` solve of a Gset-shaped N=2000 Max-Cut on a
  one-die mesh; its cut from the energy must equal the cut of its spins.
* service — a few requests through ``IsingService(solver="engine")`` with
  no fault plan and no fallback: no fallback solve may happen.
* sb-jax — one bucket; its kernel must be compiled, not interpreted.

``--four-chips`` runs only the N=2000 fabric solve on a four-die mesh of
real chips and compares it bitwise with the same solve on one die (the
mesh-invariance contract), checking that each dispatched batch spans all
four chips.

Exits non-zero, printing no result, when JAX finds no TPU, when any plan
is not the compiled fused kernel, when any fallback solve happens, or when
any check fails. The last line of a passing run is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

CHIP64 = dict(n_spins=64, density=0.5, problems=256, runs=1024)
SLICE_P, SLICE_R = 8, 128            # CPU reference slice of chip64
GSET_N = 2000
FABRIC_RUNS = 4
FABRIC_BUDGET = 0.25                 # 16 of the default 64 outer sweeps
SEED = 0


class Smoke:
    """Per-phase lines on stdout; failed checks collected, not raised, so
    one run reports every failure."""

    def __init__(self):
        self.failures: list[str] = []

    def line(self, phase: str, text: str) -> None:
        print(f"[{phase}] {text}", flush=True)

    def check(self, phase: str, ok: bool, what: str) -> None:
        self.line(phase, f"{'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(f"{phase}: {what}")

    def plan(self, phase: str, plan: dict, reason: str = "auto") -> None:
        self.line(phase, f"plan path={plan['path']} "
                         f"block_r={plan['block_r']} "
                         f"j_dtype={plan['j_dtype']} "
                         f"interpret={plan['interpret']} ({plan['reason']})")
        self.check(phase, plan["path"] == "fused"
                   and plan["interpret"] is False
                   and plan["reason"] == reason,
                   f"plan is the compiled fused kernel (reason={reason})")


def _f64_energy(J, s):
    """-0.5 s'Js in float64; J's leading axes broadcast against s's."""
    s = np.asarray(s, np.float64)
    return -0.5 * np.einsum("...i,...ij,...j->...", s,
                            np.asarray(J, np.float64), s)


def _agreement(a, b) -> str:
    a, b = np.asarray(a), np.asarray(b)
    return (f"{np.mean(a == b):.6f} of {a.size} equal "
            f"({'bitwise' if np.array_equal(a, b) else 'not bitwise'})")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_engine(sm: Smoke):
    """chip64 through launch.solve.solve, both variants, plus the slice
    references. Returns nothing; records checks on ``sm``."""
    import jax

    from repro.core import IsingMachine
    from repro.launch.solve import solve

    reports = {}
    for variant, pert in (("perturbation", True), ("gd", False)):
        phase = f"engine:{variant}"
        walls = []
        for _ in range(2):               # first call compiles
            rep, suite = solve(CHIP64["n_spins"], CHIP64["density"],
                               CHIP64["problems"], CHIP64["runs"], seed=SEED,
                               solver="engine", backend="auto",
                               perturbation=pert)
            walls.append(rep.wall_s)
        sm.plan(phase, rep.meta["engine_plan"])
        sm.line(phase, f"{rep.num_problems} problems x {rep.runs} runs x "
                       f"{CHIP64['n_spins']} spins: wall {walls[1]:.3f} s, "
                       f"compile {walls[0] - walls[1]:.3f} s "
                       f"(first call {walls[0]:.3f} s)")
        e_f64 = np.array([_f64_energy(p.J_levels, s)
                          for p, s in zip(suite, rep.best_sigma)])
        sm.check(phase, np.array_equal(e_f64, rep.best_energy),
                 "every reported best energy == float64 energy of its spins")
        reports[variant] = rep

    # one oracle for both variants: each solve reconciled the cached
    # best-known with its own best, so take the lowest of the two
    bk = np.minimum(reports["perturbation"].best_known,
                    reports["gd"].best_known)
    sr = {v: r.attach_oracle(bk).success_rate().mean()
          for v, r in reports.items()}
    for v in reports:
        sm.line(f"engine:{v}", f"SR vs oracle {sr[v]:.6f} (mean over "
                               f"{len(bk)} problems)")
    sm.check("engine", sr["perturbation"] >= sr["gd"],
             f"SR(perturbation) {sr['perturbation']:.6f} >= "
             f"SR(gd) {sr['gd']:.6f}")

    # -- slice references: same problems, same LFSR runs (a prefix of each
    # problem's 1024-run stream), same seed the registry used
    J = np.stack([p.J_levels for p in suite])[:SLICE_P].astype(np.float32)
    run_seed = SEED + 1                  # solve() -> solve_suite(seed + 1)
    cpu = jax.devices("cpu")[0]
    for variant in ("gd", "perturbation"):
        phase = f"reference:{variant}"

        def machine(backend):
            m = IsingMachine(backend=backend)
            return m.gradient_descent_baseline() if variant == "gd" else m

        chip = machine("auto").solve(J, num_runs=SLICE_R, seed=run_seed,
                                     quantize=False)
        chip_scan = machine("jnp").solve(J, num_runs=SLICE_R, seed=run_seed,
                                         quantize=False)
        with jax.default_device(cpu):
            ref = machine("jnp").solve(J, num_runs=SLICE_R, seed=run_seed,
                                       quantize=False)
        full = np.stack(reports[variant].energies[:SLICE_P])[:, :SLICE_R]
        sm.check(phase, np.array_equal(chip.energy, full),
                 f"{SLICE_P}x{SLICE_R} slice re-solved on the chip == the "
                 f"chip64 run's energies")
        sm.check(phase, np.array_equal(chip.energy,
                                       _f64_energy(J[:, None], chip.sigma)),
                 "every slice energy == float64 energy of its spins")
        sm.line(phase, f"fused (chip) vs scan (chip) spins: "
                       f"{_agreement(chip.sigma, chip_scan.sigma)}")
        sm.line(phase, f"fused (chip) vs scan (host CPU) spins: "
                       f"{_agreement(chip.sigma, ref.sigma)}")
        if variant == "gd":
            sm.check(phase, np.array_equal(chip.sigma, ref.sigma)
                     and np.array_equal(chip.energy, ref.energy),
                     "spins and energies bitwise == scan on the host CPU")


def _fabric_solve(mesh_devices: int):
    from repro.launch.solve import solve
    return solve(GSET_N, 0.5, 1, FABRIC_RUNS, seed=SEED, solver="fabric-jax",
                 backend="auto", workload="gset", mesh_devices=mesh_devices,
                 oracle=False, budget=FABRIC_BUDGET)


def _check_cut(sm: Smoke, phase: str, rep, suite) -> None:
    from repro.core.hamiltonian import maxcut_value
    from repro.problems.gset import cut_from_energy
    W = suite[0].meta["W"]
    cut_e = cut_from_energy(W, float(rep.best_energy[0]))
    cut_s = float(maxcut_value(W, rep.best_sigma[0]))
    sm.check(phase, cut_e == cut_s,
             f"cut from energy {cut_e:g} == cut of spins {cut_s:g}")


def phase_fabric(sm: Smoke) -> None:
    phase = "fabric"
    t0 = time.perf_counter()
    rep, suite = _fabric_solve(1)
    wall = time.perf_counter() - t0
    fab = rep.meta["fabric"]
    sm.plan(phase, rep.meta["engine_plan"])
    sm.line(phase, f"N={GSET_N} gset, {FABRIC_RUNS} restarts, "
                   f"{rep.meta['outer_sweeps']} sweeps, {rep.dispatches} "
                   f"dispatches on {fab['mesh_devices']} die: wall "
                   f"{wall:.3f} s (compile included)")
    _check_cut(sm, phase, rep, suite)


def phase_service(sm: Smoke) -> None:
    from repro.api import Problem
    from repro.serve import IsingService
    phase = "service"
    pool = [Problem.random_qubo(n, 0.5, seed=100 + i)
            for i, n in enumerate((64, 64, 48, 64, 32, 64, 40, 64))]
    t0 = time.perf_counter()
    with IsingService(solver="engine", runs=64, seed=SEED, cache=False,
                      max_wait_s=0.05) as svc:
        results = [t.result(timeout=600)
                   for t in [svc.submit(p) for p in pool]]
        stats = svc.stats()
        rep = svc.report()
    wall = time.perf_counter() - t0
    res = stats["resilience"]
    sm.plan(phase, rep.meta["engine_plan"])
    sm.line(phase, f"{len(results)} requests, {stats['flushes']} flushes, "
                   f"{stats['dispatches']} dispatches, wall {wall:.3f} s "
                   f"(compile included)")
    sm.line(phase, f"fallback_solves={res['fallback_solves']} "
                   f"errors={stats['errors']}")
    sm.check(phase, res["fallback_solves"] == 0 and stats["errors"] == 0,
             "no fallback solve and no error")
    sm.check(phase, all(r.solver == "engine" and not r.degraded
                        for r in results), "every answer came from engine")
    sm.check(phase, all(r.best_energy == _f64_energy(p.J_levels, r.sigma)
                        for p, r in zip(pool, results)),
             "every answer's energy == float64 energy of its spins")


def phase_sb(sm: Smoke) -> None:
    import jax

    from repro.api import ProblemSuite, get_solver
    from repro.kernels.sb_kernel import fused_sb_kernel, sb_reference
    phase = "sb-jax"
    suite = ProblemSuite.random(64, 0.5, 8, seed=3)
    t0 = time.perf_counter()
    rep = get_solver("sb-jax").solve(suite, runs=64, seed=SEED)
    wall = time.perf_counter() - t0
    e_f64 = np.array([_f64_energy(p.J_levels, s)
                      for p, s in zip(suite, rep.best_sigma)])
    sm.line(phase, f"{rep.num_problems} problems x {rep.runs} restarts, "
                   f"{rep.dispatches} dispatch: wall {wall:.3f} s "
                   f"(compile included)")
    sm.check(phase, np.array_equal(e_f64, rep.best_energy),
             "every reported best energy == float64 energy of its spins")
    # the kernel at the solver's default interpret setting
    Jc = np.asarray(suite.buckets(64)[0].J, np.float32) * 0.01
    x0 = np.random.default_rng(1).uniform(-0.1, 0.1, (8, 64, 64)) \
        .astype(np.float32)
    y0 = x0[:, ::-1].copy()
    kernel = jax.jit(lambda J, x, y: fused_sb_kernel(J, x, y, block_r=64))
    compiled = "tpu_custom_call" in kernel.lower(Jc, x0, y0).as_text()
    sm.check(phase, compiled, "sb kernel lowers to a compiled TPU kernel "
                              "(tpu_custom_call), not interpret mode")
    sm.line(phase, "kernel vs lax.scan reference (chip) positions: "
                   + _agreement(kernel(Jc, x0, y0),
                                sb_reference(Jc, x0, y0)))


def phase_four_chips(sm: Smoke) -> None:
    phase = "fabric:4"
    reps = {}
    for k in (4, 1):
        t0 = time.perf_counter()
        rep, suite = _fabric_solve(k)
        fab = rep.meta["fabric"]
        sm.line(phase, f"mesh {k}: {rep.dispatches} dispatches, batches on "
                       f"{fab['batch_devices']} device(s), wall "
                       f"{time.perf_counter() - t0:.3f} s (compile "
                       f"included), best energy {rep.best_energy[0]:g}")
        sm.check(phase, fab["batch_devices"] == k,
                 f"mesh {k}: each dispatched batch spans {k} chip(s)")
        sm.plan(phase, rep.meta["engine_plan"])
        _check_cut(sm, phase, rep, suite)
        reps[k] = rep
    a, b = reps[4], reps[1]
    sm.check(phase, np.array_equal(a.energies[0], b.energies[0])
             and np.array_equal(a.best_sigma[0], b.best_sigma[0]),
             "4-die solve bitwise == 1-die solve (energies and spins)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-die fabric solve and its "
                         "one-die comparison (needs four chips)")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.utils import enable_compile_cache

    sm = Smoke()
    sm.line("device", f"{dev.device_kind} ({dev.platform}) x {len(devs)}; "
                      f"compile cache {enable_compile_cache()}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as state:
        # fresh oracle and autotune caches: nothing tracked, nothing in ~
        os.environ["REPRO_ORACLE_CACHE"] = os.path.join(state, "oracle.json")
        os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(state,
                                                          "autotune.json")
        phases = ([phase_four_chips] if args.four_chips else
                  [phase_engine, phase_fabric, phase_service, phase_sb])
        for fn in phases:
            t0 = time.perf_counter()
            fn(sm)
            sm.line("time", f"{fn.__name__} {time.perf_counter() - t0:.3f} s")
    if sm.failures:
        print("chip_smoke FAILED:\n  " + "\n  ".join(sm.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
