"""Mega-fabric gate: weak scaling, dispatch ledger, parity, chip-lns duel.

Four hard gates over the mesh-sharded checkerboard solver
(``repro.distributed.fabric`` / registry ``fabric-jax``), per ISSUE 10:

1. **Weak scaling** — at fixed spins-per-die, per-outer-sweep wall time on
   the *fabric clock* stays flat within 25% from 1 to 8 forced host
   devices. The fabric clock is the same accounting ``serve_fleet``'s
   ``VirtualDie`` established: this container is ONE CPU core, so the
   engine's simulated anneal time (silicon's stand-in) is excluded and
   replaced by the modeled die occupancy of the batch — ``color-phase
   peak tiles/die x restarts x inner runs x DIE_US_PER_ANNEAL``, the
   quantity a real multi-die fabric overlaps — while the host-side
   orchestration (sharded field exchange, batch assembly, float64
   acceptance) is measured wall time and grows with problem size. Flat
   fabric-clock sweeps mean added dies absorb added spins.

2. **Dispatch ledger** — engine dispatches per solve == n_colors x
   outer_sweeps, never one per block (checked at every mesh size AND on
   the N=2000 duel row).

3. **Parity** — N <= 64 fabric-jax output is bit-identical to the plain
   engine solve, and large-N fabric output is bit-identical across mesh
   sizes: the mesh decides where candidates are generated, never what is
   accepted. Two invariance rows: K=1 vs K=8 at N=252 (<= 1 tile per die
   per color) AND K=1 vs K=2 at N=378, where a color class has MORE
   tiles than dies — the case that catches any acceptance loop that
   follows die-major batch order instead of canonical tile order.

4. **chip-lns duel** — on a 2000-spin Gset instance (run end-to-end:
   Gset encode -> solve -> gauge decode -> cut verify), fabric-jax beats
   sequential chip-lns fabric-clock wall time at equal solution quality
   (best cut within 2%), both tiers at identical seeds/restarts/sweeps.

On an accelerator host the mesh phases run in-process over the real
devices (a chip belongs to one process, so no child may need it), with
mesh sizes capped at the device count. On a CPU host they run in ONE
subprocess over forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count`` must be set before
jax imports); gates needing only 1 device run in-process. Writes
``BENCH_fabric.json`` at the repo root (CI archives it).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from .common import csv_line, record, write_root_bench

FORCED_DEVICES = 8
SPINS_PER_DIE = 126          # 2 tiles/die -> exactly 1 per color phase
RESTARTS = 4
INNER_RUNS = 4
ANNEAL_SWEEPS = 0.5          # shortened sim anneal (CPU is the simulator)
SEED = 1207
# modeled die occupancy per anneal — serve_fleet's VirtualDie constant
DIE_US_PER_ANNEAL = 6000.0
FLATNESS = 1.25              # gate 1: max/min fabric-clock sweep ratio
DUEL_N = 2000
DUEL_QUALITY_RTOL = 0.02
_MARK = "FABRIC_PHASE_JSON:"


def _solver(mesh_devices=None, outer_sweeps=4):
    from repro.api.registry import get_solver
    return get_solver("fabric-jax", anneal_sweeps=ANNEAL_SWEEPS,
                      inner_runs=INNER_RUNS, outer_sweeps=outer_sweeps,
                      mesh_devices=mesh_devices)


def _fabric_clock(fab: dict) -> dict:
    """Per-sweep fabric-clock seconds from a solve's fabric ledger:
    measured host orchestration (engine sim time excluded) + modeled
    concurrent die occupancy of each color phase."""
    host = [s["t_total"] - s["t_engine"] for s in fab["per_sweep"]]
    occ = sum(fab["color_peaks"]) * fab["restarts"] * fab["inner_runs"] \
        * DIE_US_PER_ANNEAL / 1e6
    per_sweep = [h + occ for h in host]
    return {"host_per_sweep_s": float(np.mean(host)),
            "modeled_occupancy_per_sweep_s": occ,
            "clock_per_sweep_s": float(np.mean(per_sweep)),
            "clock_total_s": float(np.sum(per_sweep))}


# ---------------------------------------------------------------------------
# mesh phase: everything that needs a multi-device mesh
# ---------------------------------------------------------------------------

def _phase_mesh(full: bool, n_dev: int = FORCED_DEVICES) -> dict:
    """Mesh gates over ``n_dev`` devices: forced host devices in the CPU
    subprocess, the host's chips in-process on an accelerator."""
    from repro.core.hamiltonian import maxcut_value
    from repro.problems.gset import cut_from_energy, gset_problem

    out: dict = {"weak": [], "duel": {}}

    # -- gate 1: weak scaling at fixed spins-per-die ----------------------
    sweeps = 3 if full else 2
    for k in (k for k in (1, 2, 4, 8) if k <= n_dev):
        n = SPINS_PER_DIE * k
        p = gset_problem(n, seed=SEED, degree=6.0)
        s = _solver(mesh_devices=k, outer_sweeps=sweeps)
        rep = s.solve(p, runs=RESTARTS, seed=SEED)
        fab = rep.meta["fabric"]
        clock = _fabric_clock(fab)
        expect = fab["n_colors"] * sweeps
        if rep.dispatches != expect:
            raise RuntimeError(
                f"weak-scaling K={k}: {rep.dispatches} dispatches for "
                f"{fab['n_colors']} colors x {sweeps} sweeps (expected "
                f"{expect}) — the ledger gate (one dispatch per color "
                f"phase) broke")
        out["weak"].append({
            "mesh_devices": k, "n": n, "outer_sweeps": sweeps,
            "dispatches": rep.dispatches,
            "n_tiles": fab["n_tiles"][0], "color_peaks": fab["color_peaks"],
            "best_energy": float(np.min(rep.energies[0])), **clock})
        print(f"# weak K={k} N={n}: clock/sweep="
              f"{clock['clock_per_sweep_s'] * 1e3:.1f}ms (host "
              f"{clock['host_per_sweep_s'] * 1e3:.1f}ms + die "
              f"{clock['modeled_occupancy_per_sweep_s'] * 1e3:.1f}ms)",
              flush=True)

    # -- gate 3b: mesh-size bit-invariance at fixed N ---------------------
    # Two rows: (a) N=252 over K=1 vs 8 — at most one tile per die per
    # color, and (b) N=378 over K=1 vs 2 — SIX tiles, three per color
    # class on two dies, so the die-major batch slot order differs from
    # tile order. Row (b) is the configuration a die-major acceptance
    # loop gets wrong (same-color tiles are still coupled through J, so
    # acceptance ORDER shifts the field ledger): acceptance must run in
    # canonical (problem, tile) order for this row to pass.
    out["mesh_invariance"] = []
    pairs = ((2 * SPINS_PER_DIE, (1, n_dev)),
             (3 * SPINS_PER_DIE, (1, min(2, n_dev))))
    for n_inv, k_pair in ((n, k) for n, k in pairs if k[1] > 1):
        p = gset_problem(n_inv, seed=SEED + 1, degree=6.0)
        reps = {k: _solver(mesh_devices=k, outer_sweeps=2).solve(
            p, runs=RESTARTS, seed=SEED) for k in k_pair}
        a, b = reps[k_pair[0]], reps[k_pair[1]]
        if not (np.array_equal(a.energies[0], b.energies[0])
                and np.array_equal(a.best_sigma[0], b.best_sigma[0])):
            raise RuntimeError(
                f"fabric output diverged between mesh sizes {k_pair[0]} "
                f"and {k_pair[1]} at N={n_inv} — acceptance must be "
                f"mesh-independent (canonical tile order)")
        tiles = a.meta["fabric"]["n_tiles"][0]
        out["mesh_invariance"].append(
            {"n": n_inv, "mesh_devices": list(k_pair), "n_tiles": tiles,
             "tiles_per_color_exceeds_dies": tiles // 2 > k_pair[1],
             "bit_identical": True})
        print(f"# invariance N={n_inv} K={k_pair[0]} vs {k_pair[1]}: "
              f"bit-identical ({tiles} tiles)", flush=True)

    # -- gates 2+4: the N=2000 end-to-end duel ----------------------------
    duel_sweeps = 4 if full else 2
    p = gset_problem(DUEL_N, seed=SEED + 2, degree=6.0)   # encode
    W = p.meta["W"]

    s = _solver(mesh_devices=n_dev, outer_sweeps=duel_sweeps)
    rep_f = s.solve(p, runs=RESTARTS, seed=SEED)          # solve
    fab = rep_f.meta["fabric"]
    if rep_f.dispatches != fab["n_colors"] * duel_sweeps:
        raise RuntimeError(
            f"duel row: {rep_f.dispatches} dispatches != "
            f"{fab['n_colors']} colors x {duel_sweeps} sweeps")
    fclock = _fabric_clock(fab)

    from repro.api.registry import get_solver
    s_c = get_solver("chip-lns", anneal_sweeps=ANNEAL_SWEEPS,
                     inner_runs=INNER_RUNS, outer_sweeps=duel_sweeps)
    rep_c = s_c.solve(p, runs=RESTARTS, seed=SEED)
    ct = rep_c.meta["lns_timings"]
    n_subs = rep_c.meta["n_blocks"] * RESTARTS
    c_occ = duel_sweeps * n_subs * INNER_RUNS * DIE_US_PER_ANNEAL / 1e6
    cclock = {"host_total_s": ct["t_host"],
              "modeled_occupancy_total_s": c_occ,
              "clock_total_s": ct["t_host"] + c_occ}

    # decode + verify: gauge is free (bias-free J), cut from spins must
    # match cut from energy exactly — integer weights, exact arithmetic
    sigma = np.asarray(rep_f.best_sigma[0])
    e_best = float(np.min(rep_f.energies[0]))
    cut_sigma = float(maxcut_value(W, sigma))
    cut_e = cut_from_energy(W, e_best)
    if cut_sigma != cut_e:
        raise RuntimeError(f"N={DUEL_N} decode/verify mismatch: cut from "
                           f"spins {cut_sigma} != cut from energy {cut_e}")

    e_fab = float(np.min(rep_f.energies[0]))
    e_chip = float(np.min(rep_c.energies[0]))
    if e_fab > e_chip + DUEL_QUALITY_RTOL * abs(e_chip):
        raise RuntimeError(
            f"duel quality: fabric best {e_fab} worse than chip-lns "
            f"{e_chip} beyond {DUEL_QUALITY_RTOL:.0%} — speed without "
            f"quality doesn't count")
    if fclock["clock_total_s"] >= cclock["clock_total_s"]:
        raise RuntimeError(
            f"duel wall: fabric clock {fclock['clock_total_s']:.2f}s not "
            f"below sequential chip-lns {cclock['clock_total_s']:.2f}s at "
            f"N={DUEL_N}")
    out["duel"] = {
        "n": DUEL_N, "outer_sweeps": duel_sweeps,
        "mesh_devices": n_dev,
        "fabric": {"best_energy": e_fab, "best_cut": cut_sigma,
                   "dispatches": rep_f.dispatches, **fclock},
        "chip_lns": {"best_energy": e_chip,
                     "best_cut": cut_from_energy(W, e_chip),
                     "dispatches": rep_c.dispatches, **cclock},
        "speedup": cclock["clock_total_s"] / fclock["clock_total_s"],
        "verified": True}
    print(f"# duel N={DUEL_N}: fabric {fclock['clock_total_s']:.2f}s vs "
          f"chip-lns {cclock['clock_total_s']:.2f}s "
          f"(x{out['duel']['speedup']:.1f}), cut {cut_sigma:.0f} vs "
          f"{out['duel']['chip_lns']['best_cut']:.0f}", flush=True)
    return out


def _run_mesh_subprocess(full: bool) -> dict:
    env = dict(os.environ)
    flag = f"--xla_force_host_platform_device_count={FORCED_DEVICES}"
    if flag not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "benchmarks.fabric_scaling",
           "--phase", "mesh"] + (["--full"] if full else [])
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          cwd=os.path.dirname(src))
    sys.stdout.write("".join(
        ln + "\n" for ln in proc.stdout.splitlines()
        if not ln.startswith(_MARK)))
    if proc.returncode != 0:
        raise RuntimeError(f"fabric mesh phase failed "
                           f"(rc={proc.returncode}):\n{proc.stderr[-4000:]}")
    for ln in proc.stdout.splitlines():
        if ln.startswith(_MARK):
            return json.loads(ln[len(_MARK):])
    raise RuntimeError(f"fabric mesh phase emitted no result marker:\n"
                       f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")


# ---------------------------------------------------------------------------
# in-process phase: 1-device parity gate + orchestration
# ---------------------------------------------------------------------------

def _phase_parity() -> dict:
    """Gate 3a: N <= 64 fabric-jax == plain engine, bitwise."""
    from repro.api import Problem
    from repro.api.registry import get_solver
    p = Problem.maxcut(48, density=0.5, seed=SEED)
    kw = dict(runs=8, seed=SEED)
    # the N<=64 delegation runs the engine's own default anneal length,
    # so parity is against the stock engine solver
    rep_f = get_solver("fabric-jax").solve(p, **kw)
    rep_e = get_solver("engine").solve(p, **kw)
    if not (np.array_equal(rep_f.energies[0], rep_e.energies[0])
            and np.array_equal(rep_f.best_sigma[0], rep_e.best_sigma[0])):
        raise RuntimeError("N=48 fabric-jax output is not bit-identical "
                           "to the plain engine solve")
    return {"n": 48, "runs": 8, "bit_identical": True}


def run(full: bool = False):
    import jax
    t0 = time.time()
    parity = _phase_parity()
    on_cpu = jax.default_backend() == "cpu"
    n_dev = FORCED_DEVICES if on_cpu else len(jax.devices())
    mesh = (_run_mesh_subprocess(full) if on_cpu
            else _phase_mesh(full, n_dev=n_dev))

    clocks = [w["clock_per_sweep_s"] for w in mesh["weak"]]
    flatness = max(clocks) / min(clocks)
    if flatness > FLATNESS:
        worst = max(mesh["weak"], key=lambda w: w["clock_per_sweep_s"])
        raise RuntimeError(
            f"weak scaling: fabric-clock per-sweep spread x{flatness:.2f} "
            f"exceeds x{FLATNESS:.2f} across 1..{n_dev} dies "
            f"(worst K={worst['mesh_devices']} at "
            f"{worst['clock_per_sweep_s'] * 1e3:.1f}ms/sweep)")

    payload = {
        "spins_per_die": SPINS_PER_DIE, "restarts": RESTARTS,
        "inner_runs": INNER_RUNS, "anneal_sweeps": ANNEAL_SWEEPS,
        "die_us_per_anneal": DIE_US_PER_ANNEAL,
        "mesh_devices": n_dev,
        "platform": jax.default_backend(),
        "weak_scaling": mesh["weak"],
        "weak_scaling_flatness": flatness,
        "flatness_gate": FLATNESS,
        "dispatches_per_solve": "n_colors * outer_sweeps",
        "mesh_invariance": mesh["mesh_invariance"],
        "engine_parity_n64": parity,
        "duel_n2000": mesh["duel"],
    }
    record("fabric_scaling", payload)
    write_root_bench("BENCH_fabric.json", payload)

    n_solves = len(mesh["weak"]) + 2 * len(mesh["mesh_invariance"]) + 4
    us = (time.time() - t0) * 1e6 / n_solves
    duel = mesh["duel"]
    inv = ",".join(f"N{r['n']}:K{r['mesh_devices'][0]}-"
                   f"{r['mesh_devices'][1]}"
                   for r in mesh["mesh_invariance"])
    print(csv_line(
        "fabric_scaling", us,
        f"flatness=x{flatness:.2f};"
        f"duel_speedup=x{duel['speedup']:.1f};"
        f"duel_cut={duel['fabric']['best_cut']:.0f};"
        f"parity=bit_identical;mesh_invariant={inv}"))
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=["mesh"], default=None,
                    help="internal: run the forced-multi-device phase "
                         "in-process and print its JSON marker")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.phase == "mesh":
        result = _phase_mesh(full=args.full)
        print(_MARK + json.dumps(result, default=float), flush=True)
    else:
        run(full=args.full)
