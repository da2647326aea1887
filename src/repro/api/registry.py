"""Solver protocol + registry — one ``solve()`` surface over every backend.

Every solver in the repo (the AnnealEngine-backed digital twin, the JAX and
numpy simulated-annealing baselines, the tabu oracle, exhaustive brute
force) registers here behind one signature:

    solver = get_solver("engine")
    report = solver.solve(suite, runs=256, seed=0, budget=None)

``suite`` may be a :class:`ProblemSuite`, a single :class:`Problem`, or a
raw coupling matrix / batch (wrapped automatically). ``runs`` is the number
of independent runs/restarts per problem; ``budget`` is a solver-relative
effort multiplier (anneal length for the engine, sweeps for SA, iterations
for tabu; exact solvers ignore it). All solvers bucket heterogeneous suites
by padded size, so a mixed 16/32/64-spin sweep costs one device dispatch
per bucket — ``SolveReport.dispatches`` records the count.

Capability flags (``SolverCaps``) tell callers what each solver needs:
``needs_oracle`` (heuristic — success metrics require a best-known
reference), ``exact`` (its own energies ARE ground truth), ``device``
("jax" batched vs "numpy" host loop), and ``max_n``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Protocol, runtime_checkable

import numpy as np

from ..solvers.brute_force import BRUTE_FORCE_MAX_N
from ..tracing import span
from .batching import CHIP_BLOCK, padded_size, plan_buckets
from .budget import budget_factor, search_effort
from .oracle import best_known_energies, reconcile_best_known
from .problem import Problem
from .report import SolveReport
from .suite import ProblemSuite


@dataclasses.dataclass(frozen=True)
class SolverCaps:
    needs_oracle: bool                # success metrics need external best-known
    exact: bool                       # returned energies are ground truth
    device: str                       # 'jax' (batched) | 'numpy' (host loop)
    max_n: Optional[int] = None       # hard size limit, if any


@runtime_checkable
class Solver(Protocol):
    name: str
    caps: SolverCaps

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport: ...


_REGISTRY: dict[str, type] = {}


def register_solver(name: str, *, needs_oracle: bool, exact: bool,
                    device: str, max_n: Optional[int] = None):
    """Class decorator: publish a Solver implementation under ``name``."""
    caps = SolverCaps(needs_oracle=needs_oracle, exact=exact,
                      device=device, max_n=max_n)

    def deco(cls):
        cls.name = name
        cls.caps = caps
        _REGISTRY[name] = cls
        return cls
    return deco


def list_solvers() -> dict[str, SolverCaps]:
    return {name: cls.caps for name, cls in sorted(_REGISTRY.items())}


def get_solver(name: str, **opts) -> Solver:
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown solver {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None
    return cls(**opts)


class SolverWrapper:
    """Delegating base for solver interposers.

    A wrapper satisfies the :class:`Solver` protocol by forwarding
    ``name``/``caps``/``solve`` to the wrapped instance, so anything that
    consumes a registered solver (``solve_suite``, the serve tier's flush
    executor, benchmarks) accepts a wrapped one transparently. Subclass and
    override ``solve`` to interpose — the serve tier's deterministic fault
    injector (``repro.serve.faults.FaultySolver``) and test shims (flaky /
    poisoned solvers) are built on this.
    """

    def __init__(self, inner: Solver):
        self.inner = inner

    @property
    def name(self) -> str:
        return self.inner.name

    @property
    def caps(self) -> SolverCaps:
        return self.inner.caps

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        return self.inner.solve(suite, runs=runs, seed=seed, budget=budget,
                                block=block)


def as_suite(problems) -> ProblemSuite:
    """Normalize Problem / ProblemSuite / raw (N,N) or (P,N,N) couplings."""
    if isinstance(problems, ProblemSuite):
        return problems
    if isinstance(problems, Problem):
        return ProblemSuite([problems])
    J = np.asarray(problems)
    if J.ndim == 2:
        J = J[None]
    return ProblemSuite([Problem.from_couplings(j) for j in J])


def solve_suite(problems, solver: str = "engine", runs: int = 64,
                seed: int = 0, budget: Optional[float] = None,
                block: int = CHIP_BLOCK, oracle: bool = True,
                use_cache: bool = True, oracle_path: Optional[str] = None,
                **solver_opts) -> SolveReport:
    """One-call entry point: solve + (optionally) attach the best-known
    oracle so ``report.metrics()`` works immediately."""
    suite = as_suite(problems)
    sol = get_solver(solver, **solver_opts)
    report = sol.solve(suite, runs=runs, seed=seed, budget=budget,
                       block=block)
    if oracle:
        if sol.caps.needs_oracle:
            # Heuristic solver: external best-known, upgraded in place if
            # this solve happened to beat a stale cached entry.
            bk = best_known_energies(suite, use_cache=use_cache,
                                     path=oracle_path)
            bk = reconcile_best_known(
                suite, np.minimum(bk, report.best_energy),
                use_cache=use_cache, path=oracle_path,
                method=f"improved:{sol.name}")
        else:
            # The solver IS an oracle (tabu / brute force): reuse its own
            # energies instead of running the oracle a second time, still
            # reconciled against anything better already cached. Only
            # exact solvers may seed missing entries (ground truth).
            bk = reconcile_best_known(
                suite, report.best_energy, use_cache=use_cache,
                path=oracle_path, method=f"self:{sol.name}",
                write_missing=sol.caps.exact)
        report.attach_oracle(bk)
    return report


# ---------------------------------------------------------------------------
# implementations
# ---------------------------------------------------------------------------

def _check_max_n(suite: ProblemSuite, caps: SolverCaps, name: str,
                 block: int = CHIP_BLOCK) -> None:
    """Enforce a solver's declared capacity BEFORE any padding happens.

    ``padded_size`` happily pads an N=65 problem to a 128-spin batch, which
    a capacity-limited solver would then silently solve as a virtual
    two-die chip that doesn't exist. Every registered solver calls this at
    the top of ``solve``; solvers without a limit declare ``max_n=None``.
    """
    if caps.max_n is None:
        return
    big = max(suite.sizes, default=0)
    if big > caps.max_n:
        pad = padded_size(big, block)
        raise ValueError(
            f"solver {name!r} declares max_n={caps.max_n} but the suite has "
            f"N={big} (would pad to a {pad}-spin virtual chip); use the "
            f"'chip-lns' decomposition solver for problems beyond one "
            f"{caps.max_n}-spin block")


def _bucketed_report(suite, solver_name, runs, block, run_bucket,
                     meta=None, buckets=None, warmup=False) -> SolveReport:
    """Shared bucket loop: run ``run_bucket(bucket, b_idx) -> (e, s)`` with
    ``e (P, R)`` level-space energies and ``s (P, R, n_pad)`` spins; trim
    and reorder into suite order via the shared planner
    (``api.batching.BatchPlan.scatter``). Pass ``buckets`` if already built
    (the padded batches are the expensive part — don't stack them twice).

    With ``warmup`` each bucket is dispatched twice: the first call pays
    XLA compilation/tracing, the second is timed. ``wall_s`` then measures
    steady-state solve time (what ``anneals_per_s`` should charge the
    solver for) and ``compile_s`` the one-time difference — seeds are
    per-bucket deterministic, so both calls return identical results."""
    plan = plan_buckets(suite.sizes, block)
    buckets = buckets if buckets is not None else suite.buckets(block)
    outputs = []
    wall = compile_s = 0.0
    for b_idx, bucket in enumerate(buckets):
        if warmup:
            t0 = time.perf_counter()
            for arr in run_bucket(bucket, b_idx):
                np.asarray(arr)                    # force device sync
            t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        e, s = run_bucket(bucket, b_idx)
        with span("registry.scatter"):
            e = np.asarray(e, dtype=np.float64)
            s = np.asarray(s)
        dt = time.perf_counter() - t0
        wall += dt
        if warmup:
            compile_s += max(0.0, t_first - dt)
        outputs.append((e, s))
    with span("registry.scatter"):
        energies, sigmas = plan.scatter(outputs)
    return SolveReport(
        solver=solver_name, runs=runs, energies=energies, best_sigma=sigmas,
        problem_hashes=suite.hashes, sizes=suite.sizes,
        scales=tuple(p.scale for p in suite), wall_s=wall,
        compile_s=compile_s, dispatches=len(buckets), meta=meta or {})


@register_solver("engine", needs_oracle=True, exact=False, device="jax",
                 max_n=CHIP_BLOCK)
class EngineSolver:
    """The digital twin: IsingMachine -> AnnealEngine (scan/fused paths).

    Capacity: ONE 64-spin die (``max_n=CHIP_BLOCK``) — the chip the paper
    characterizes. Larger instances must go through the 'chip-lns'
    decomposition solver, which drives this same engine block-by-block.

    ``variant``: 'perturbation' (paper default), 'gd' (no-perturbation
    gradient-descent baseline), 'noise' (inherent-circuit-noise baseline —
    actually seeds the noise RNG, unlike the legacy scripts which asked for
    noise but never passed a key). ``budget`` multiplies the anneal length
    (sweeps). Couplings are passed in level space with ``quantize=False`` —
    the legacy path re-quantized, silently stretching any instance whose
    strongest coupling was below ±15.
    """

    def __init__(self, backend: str = "auto", autotune: bool = False,
                 variant: str = "perturbation", machine=None,
                 noise_sigma: float = 2.0, warmup: bool = False):
        if variant not in ("perturbation", "gd", "noise"):
            raise ValueError(f"unknown engine variant {variant!r}")
        self.backend = backend
        self.autotune = autotune
        self.variant = variant
        self.noise_sigma = noise_sigma
        self.warmup = warmup
        self._machine = machine

    def _make_machine(self, budget: Optional[float]):
        import dataclasses as dc

        from ..core.device_model import DeviceModel
        from ..core.machine import IsingMachine
        if self._machine is not None:
            return self._machine
        # a fresh engine reads the autotune cache file: inside the span
        with span("registry.make_machine"):
            dev = DeviceModel()
            if budget is not None:
                dev = dc.replace(dev, anneal_sweeps=dev.anneal_sweeps *
                                 budget_factor(budget))
            m = IsingMachine(device=dev, backend=self.backend,
                             autotune=self.autotune)
            if self.variant == "gd":
                m = m.gradient_descent_baseline()
            elif self.variant == "noise":
                m = m.inherent_noise_baseline(self.noise_sigma)
        return m

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        import jax

        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        with span("registry.solve", problems=len(suite), runs=runs):
            machine = self._make_machine(budget)

            def run_bucket(bucket, b_idx):
                key = (jax.random.PRNGKey(seed + 10007 * b_idx)
                       if self.variant == "noise" else None)
                out = machine.solve(bucket.J, num_runs=runs,
                                    seed=seed + 7919 * b_idx, key=key,
                                    quantize=False)
                return out.energy, out.sigma

            buckets = suite.buckets(block)
            rep = _bucketed_report(suite, self.name, runs, block, run_bucket,
                                   meta={"variant": self.variant,
                                         "backend": self.backend},
                                   buckets=buckets, warmup=self.warmup)
            # Report the plan the biggest bucket ACTUALLY resolved to: with the
            # real J (int8 auto-select needs concrete levels) and the noise
            # variant's forced-scan feature flag.
            big = max(buckets, key=lambda b: b.n_pad)
            needs_scan = (self.variant == "noise" and
                          machine.device.noise_sigma > 0)
            plan = machine.engine.plan(big.num_problems, runs, big.n_pad,
                                       J=big.J, needs_scan=needs_scan)
            rep.meta["engine_plan"] = dataclasses.asdict(plan)
            return rep


@register_solver("sa-jax", needs_oracle=True, exact=False, device="jax")
class SAJaxSolver:
    """On-device Metropolis SA (vmapped restarts x problems); rides the same
    bucketed batches as the engine. ``budget`` multiplies sweep count."""

    def __init__(self, n_sweeps: int = 200, beta0: float = 0.05,
                 beta1: float = 4.0, warmup: bool = False):
        self.n_sweeps = n_sweeps
        self.beta0 = beta0
        self.beta1 = beta1
        self.warmup = warmup

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..solvers.sa_jax import simulated_annealing_jax_runs
        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        eff = search_effort(self.n_sweeps, runs, budget)

        def run_bucket(bucket, b_idx):
            return simulated_annealing_jax_runs(
                bucket.J, n_runs=eff.restarts, n_sweeps=eff.iters,
                beta0=self.beta0, beta1=self.beta1, seed=seed + 7919 * b_idx)

        return _bucketed_report(suite, self.name, runs, block, run_bucket,
                                meta={"n_sweeps": eff.iters,
                                      "effort": dataclasses.asdict(eff)},
                                warmup=self.warmup)


@register_solver("sa-numpy", needs_oracle=True, exact=False, device="numpy")
class SANumpySolver:
    """Host-side SA reference (one vectorized-restart call per problem)."""

    def __init__(self, n_sweeps: int = 200, beta0: float = 0.05,
                 beta1: float = 4.0):
        self.n_sweeps = n_sweeps
        self.beta0 = beta0
        self.beta1 = beta1

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..solvers.sa import simulated_annealing
        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        eff = search_effort(self.n_sweeps, runs, budget)
        energies, sigmas = [], []
        t0 = time.time()
        for i, p in enumerate(suite):
            e, s = simulated_annealing(
                p.J_levels, n_sweeps=eff.iters, n_restarts=eff.restarts,
                beta0=self.beta0, beta1=self.beta1, seed=seed + 31 * i,
                return_all=True)
            energies.append(np.asarray(e, dtype=np.float64))
            sigmas.append(s[int(np.argmin(e))])
        return SolveReport(
            solver=self.name, runs=runs, energies=energies,
            best_sigma=sigmas, problem_hashes=suite.hashes,
            sizes=suite.sizes, scales=tuple(p.scale for p in suite),
            wall_s=time.time() - t0, dispatches=0,
            meta={"n_sweeps": eff.iters, "host_evals": len(suite)})


@register_solver("tabu", needs_oracle=False, exact=False, device="numpy")
class TabuSolver:
    """qbsolv-style tabu search — the paper's best-known oracle. ``runs``
    maps to independent restarts (per-restart energies reported); ``budget``
    multiplies the per-restart iteration count (default 40*N).

    ``meta["iters_used"]`` records the flips each restart ACTUALLY applied
    — a restart stops early when every move is tabu and none aspirates, so
    charging it the full ``n_iters`` would overstate the search effort."""

    def __init__(self, tenure: Optional[int] = None):
        self.tenure = tenure

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..solvers.tabu import tabu_search
        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        energies, sigmas, iters_used, n_iters = [], [], [], []
        t0 = time.time()
        for i, p in enumerate(suite):
            eff = search_effort(40 * p.n, runs, budget)
            e, s, used = tabu_search(
                p.J_levels, n_iters=eff.iters, n_restarts=eff.restarts,
                tenure=self.tenure, seed=seed + 31 * i, return_all=True,
                return_iters=True)
            energies.append(np.asarray(e, dtype=np.float64))
            sigmas.append(s[int(np.argmin(e))])
            iters_used.append(used.tolist())
            n_iters.append(eff.iters)
        return SolveReport(
            solver=self.name, runs=runs, energies=energies,
            best_sigma=sigmas, problem_hashes=suite.hashes,
            sizes=suite.sizes, scales=tuple(p.scale for p in suite),
            wall_s=time.time() - t0, dispatches=0,
            meta={"n_iters": n_iters, "iters_used": iters_used,
                  "host_evals": len(suite)})


@register_solver("tabu-jax", needs_oracle=False, exact=False, device="jax")
class TabuJaxSolver:
    """The tabu oracle at machine batch scale: ``solvers.tabu_jax`` —
    vmapped restarts × problems, ``lax.scan`` iterations, one dispatch per
    pad bucket. Same algorithm and per-problem budgets as the numpy
    ``tabu`` solver (``n_iters = 40 * N * budget``, tenure ``max(4, N //
    4)``); padded spins are masked out of the candidate move set, so a
    bucketed suite solves exactly the problems it contains.

    ``meta["iters_used"]`` is honest per-restart effort (stalled restarts
    stop early, exactly like numpy's ``break``)."""

    def __init__(self, tenure: Optional[int] = None, warmup: bool = False):
        self.tenure = tenure
        self.warmup = warmup

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..solvers.tabu_jax import tabu_search_jax_runs
        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        efforts = [search_effort(40 * p.n, runs, budget) for p in suite]
        restarts = efforts[0].restarts if efforts else max(1, runs)
        used_by_problem = {}

        def run_bucket(bucket, b_idx):
            e, s, used = tabu_search_jax_runs(
                bucket.J,
                n_true=[suite[i].n for i in bucket.indices],
                n_iters=[efforts[i].iters for i in bucket.indices],
                n_restarts=restarts, tenure=self.tenure,
                seed=seed + 7919 * b_idx)
            for k, i in enumerate(bucket.indices):
                used_by_problem[i] = used[k].tolist()
            return e, s

        rep = _bucketed_report(
            suite, self.name, runs, block, run_bucket,
            meta={"n_iters": [e.iters for e in efforts]},
            warmup=self.warmup)
        rep.meta["iters_used"] = [used_by_problem[i]
                                  for i in range(len(suite))]
        return rep


@register_solver("pt-jax", needs_oracle=True, exact=False, device="jax")
class PTJaxSolver:
    """Replica-exchange parallel tempering (``solvers.pt_jax``) on the
    shared Metropolis sweep kernel: K fixed temperature rungs per restart,
    checkerboard neighbor swaps, everything vmapped — one dispatch per pad
    bucket. ``runs`` is independent PT restarts (each reports its
    across-rung best); ``budget`` multiplies the sweep count per the
    uniform ``search_effort`` mapping; rungs are internal parallelism.

    ``meta["swap_acceptances"]`` (mean per restart) is the mixing
    diagnostic — 0 means the ladder is too steep to exchange."""

    def __init__(self, n_sweeps: int = 120, n_rungs: int = 4,
                 beta0: float = 0.05, beta1: float = 4.0,
                 swap_every: int = 1, warmup: bool = False):
        self.n_sweeps = n_sweeps
        self.n_rungs = n_rungs
        self.beta0 = beta0
        self.beta1 = beta1
        self.swap_every = swap_every
        self.warmup = warmup

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..solvers.pt_jax import parallel_tempering_jax_runs
        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        eff = search_effort(self.n_sweeps, runs, budget,
                            rungs=self.n_rungs)
        swaps_by_problem = {}

        def run_bucket(bucket, b_idx):
            e, s, swaps = parallel_tempering_jax_runs(
                bucket.J, n_runs=eff.restarts, n_sweeps=eff.iters,
                n_rungs=eff.rungs, beta0=self.beta0, beta1=self.beta1,
                swap_every=self.swap_every, seed=seed + 7919 * b_idx)
            for k, i in enumerate(bucket.indices):
                swaps_by_problem[i] = float(np.mean(swaps[k]))
            return e, s

        rep = _bucketed_report(
            suite, self.name, runs, block, run_bucket,
            meta={"effort": dataclasses.asdict(eff)}, warmup=self.warmup)
        rep.meta["swap_acceptances"] = [swaps_by_problem[i]
                                        for i in range(len(suite))]
        return rep


@register_solver("sb-jax", needs_oracle=True, exact=False, device="jax")
class SBJaxSolver:
    """Simulated bifurcation (``solvers.sb_jax``) — the state-of-the-art
    classical competitor on dense Max-Cut, run as a fused Pallas kernel
    (``kernels.sb_kernel``): position/momentum symplectic updates over
    (problems × restarts), the linear pump ramp derived in-kernel from the
    step index, inelastic walls for bSB/dSB, ``sign_pm1`` readout — one
    dispatch per pad bucket.

    ``variant``: 'bSB' (default — ballistic, the robust all-rounder),
    'dSB' (discrete drive, strongest on dense Max-Cut), 'aSB' (the
    original adiabatic Kerr form). ``budget`` multiplies the integration
    step count per the uniform ``search_effort`` mapping; the per-problem
    coupling scale c0 is derived from each problem's TRUE size, so padded
    buckets normalize exactly like unpadded solves.
    """

    def __init__(self, variant: str = "bSB", n_steps: int = 400,
                 dt: float = 0.5, a0: float = 1.0, warmup: bool = False):
        from ..kernels.sb_kernel import SB_VARIANTS
        if variant not in SB_VARIANTS:
            raise ValueError(f"variant must be one of {SB_VARIANTS}, "
                             f"got {variant!r}")
        self.variant = variant
        self.n_steps = n_steps
        self.dt = dt
        self.a0 = a0
        self.warmup = warmup

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..solvers.sb_jax import simulated_bifurcation_jax_runs
        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        eff = search_effort(self.n_steps, runs, budget)

        def run_bucket(bucket, b_idx):
            return simulated_bifurcation_jax_runs(
                bucket.J,
                n_true=[suite[i].n for i in bucket.indices],
                variant=self.variant, n_steps=eff.iters,
                n_restarts=eff.restarts, dt=self.dt, a0=self.a0,
                seed=seed + 7919 * b_idx)

        return _bucketed_report(
            suite, self.name, runs, block, run_bucket,
            meta={"variant": self.variant, "dt": self.dt, "a0": self.a0,
                  "effort": dataclasses.asdict(eff)},
            warmup=self.warmup)


@register_solver("fabric-jax", needs_oracle=True, exact=False, device="jax")
class FabricSolver:
    """Large-neighborhood search over a mesh of 64-spin dies
    (``distributed.fabric.FabricLNS``) — no capacity limit, on the chip's
    anneal path.

    Problems with N <= ``block`` are delegated verbatim to the direct
    engine solve (same machine, same seeds — bit-identical energies), so
    the solver is a strict superset of 'engine'. Larger problems iterate:
    clamp all but one (block-1)-spin tile, anneal the tile plus one
    boundary-field ancilla as exactly one die, and accept candidate tile
    configurations by exact float64 delta energy (monotone incumbents).
    'fabric-jax' 2-colors the tile grid and anneals every tile of a color
    class concurrently across the device mesh: ``n_colors x
    outer_sweeps`` engine dispatches per solve — never one per tile — with
    the clamped-spin boundary fields computed on-mesh as sharded ``J_tile
    @ s`` row-sums. Level-space fields are integer-exact in float32, so
    results are bit-identical for every mesh size. ``runs`` is the number
    of independent LNS restarts; ``budget`` multiplies the outer sweep
    count (the engine delegation for small problems keeps its own default
    anneal length).

    ``mesh_devices`` picks how many local devices form the fabric
    (default: all — 1 on an unforced host; run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for an 8-die
    fabric). ``meta['fabric']`` carries the per-color occupancy/timing
    ledger.
    """

    #: color phases per outer sweep (the checkerboard)
    colors = 2

    def __init__(self, backend: str = "auto", inner_runs: int = 8,
                 outer_sweeps: Optional[int] = None,
                 anneal_sweeps: Optional[float] = None,
                 mesh_devices: Optional[int] = None,
                 warmup: bool = False):
        self.backend = backend
        self.inner_runs = inner_runs
        self.outer_sweeps = outer_sweeps
        self.anneal_sweeps = anneal_sweeps
        self.mesh_devices = mesh_devices
        self.warmup = warmup

    def _engine(self):
        import dataclasses as dc

        from ..core.device_model import DeviceModel
        from ..core.engine import AnnealEngine
        from ..core.machine import _BACKEND_TO_PATH
        dev = DeviceModel()
        if self.anneal_sweeps:
            dev = dc.replace(dev, anneal_sweeps=self.anneal_sweeps)
        return AnnealEngine(device=dev, path=_BACKEND_TO_PATH[self.backend])

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..distributed.fabric import FabricLNS, fabric_mesh, lns_blocks
        suite = as_suite(suite)
        with span("registry.solve", problems=len(suite), runs=runs):
            wall = 0.0
            # Delegation threshold: the direct engine can only take what
            # BOTH the requested block and its own die cap allow — with
            # block > 64 the oversized problems must still decompose, not
            # bounce off the engine's max_n check.
            delegate_n = min(block, EngineSolver.caps.max_n or block)
            small = [i for i, n in enumerate(suite.sizes) if n <= delegate_n]
            big = [i for i, n in enumerate(suite.sizes) if n > delegate_n]

            energies = [None] * len(suite)
            sigmas = [None] * len(suite)
            dispatches = 0
            compile_s = 0.0
            meta = {"block": block, "inner_runs": self.inner_runs,
                    "lns_problems": big}

            if small:
                sub = ProblemSuite([suite[i] for i in small])
                rep = EngineSolver(backend=self.backend,
                                   warmup=self.warmup).solve(
                    sub, runs=runs, seed=seed, budget=None, block=delegate_n)
                for k, i in enumerate(small):
                    energies[i] = rep.energies[k]
                    sigmas[i] = rep.best_sigma[k]
                dispatches += rep.dispatches
                compile_s += rep.compile_s
                wall += rep.wall_s
                meta["engine_plan"] = rep.meta.get("engine_plan")

            if big:
                n_blocks = max(len(lns_blocks(suite[i].n, delegate_n - 1))
                               for i in big)
                outer = self.outer_sweeps or max(4, 2 * n_blocks)
                outer = search_effort(outer, runs, budget).iters
                # the die is delegate_n, never the (possibly larger) pad
                # block: block=128 must decompose onto real 64-spin dies,
                # not anneal a 128-spin virtual chip the capability check
                # exists to forbid
                mesh = fabric_mesh(self.mesh_devices)
                lns = FabricLNS(self._engine(), mesh=mesh,
                                chip_block=delegate_n,
                                inner_runs=self.inner_runs,
                                colors=self.colors)
                big_J = [suite[i].J_levels.astype(np.float64) for i in big]
                if self.warmup:
                    # same compile/steady split as _bucketed_report: pay
                    # the trace on a discarded identical solve
                    # (deterministic seed), time the second
                    tw = time.time()
                    lns.solve(big_J, restarts=runs, outer_sweeps=outer,
                              seed=seed + 104729)
                    t_first = time.time() - tw
                t0 = time.time()
                results, d = lns.solve(big_J, restarts=runs,
                                       outer_sweeps=outer, seed=seed + 104729)
                if self.warmup:
                    compile_s += max(0.0, t_first - (time.time() - t0))
                dispatches += d
                meta["outer_sweeps"] = outer
                meta["fabric"] = lns.ledger
                # the plan the die-aligned color-phase batches dispatched under
                meta["engine_plan"] = dataclasses.asdict(lns.engine.plan(
                    max(lns.ledger["color_peaks"]) * lns.n_dies * runs,
                    self.inner_runs, delegate_n))
                meta["init_energies"] = {}
                for (e, s, e0), i in zip(results, big):
                    energies[i] = e
                    sigmas[i] = s[int(np.argmin(e))]
                    meta["init_energies"][i] = e0.tolist()
                wall += time.time() - t0

            # wall accumulates the component solve times, so warmup compile
            # paid inside the engine delegation is never charged to the solve
            return SolveReport(
                solver=self.name, runs=runs, energies=energies,
                best_sigma=sigmas, problem_hashes=suite.hashes,
                sizes=suite.sizes, scales=tuple(p.scale for p in suite),
                wall_s=wall, compile_s=compile_s, dispatches=dispatches,
                meta=meta)


@register_solver("chip-lns", needs_oracle=True, exact=False, device="jax")
class ChipLNSSolver(FabricSolver):
    """One color on one die of the fabric's LNS: every (problem, restart,
    tile) sub-instance of an outer sweep rides ONE device dispatch, so the
    dispatch ledger is one per outer sweep."""

    colors = 1

    def __init__(self, backend: str = "auto", inner_runs: int = 8,
                 outer_sweeps: Optional[int] = None,
                 anneal_sweeps: Optional[float] = None,
                 warmup: bool = False):
        super().__init__(backend=backend, inner_runs=inner_runs,
                         outer_sweeps=outer_sweeps,
                         anneal_sweeps=anneal_sweeps, mesh_devices=1,
                         warmup=warmup)


@register_solver("ode-jax", needs_oracle=True, exact=False, device="jax",
                 max_n=CHIP_BLOCK)
class OdeSolver:
    """The analog device-physics tier (``repro.physics``): continuous-time
    coupled nodal ODEs — saturating sigma nonlinearity, bistable latch,
    RC relaxation, thermal noise — driven by the same column-refresh /
    leakage / perturbation schedule as the discrete engine, integrated
    fixed-step (Euler–Maruyama or stochastic Heun) under one ``lax.scan``
    and vmapped over (chips x problems x restarts): a variation-aware
    virtual-chip fleet costs ONE device dispatch per pad bucket.

    ``variation`` (a :class:`repro.physics.VariationModel`) + ``n_chips``
    turn one solve into a fleet sweep: per-chip J mismatch, leakage
    spread, refresh jitter and gain offsets are deterministic seeded draws
    (``chip_seed``), and every chip's runs land in the report (``runs``
    restarts x ``n_chips`` chips rows per problem, chip-major).
    ``variant='gd'`` is the no-perturbation ideal-refresh baseline, like
    the engine's. In the zero-variation, zero-noise ``DISCRETE_LIMIT``
    the tier reproduces the discrete engine bit-for-bit (CI-gated in
    ``BENCH_device.json``). Energies are recomputed on the host in
    float64 from the returned spins against the NOMINAL couplings — the
    imperfect chip is scored on the ideal problem.
    """

    def __init__(self, variant: str = "perturbation", params=None,
                 variation=None, n_chips: int = 1, chip_seed: int = 0,
                 warmup: bool = False):
        from ..physics import DEFAULT_PHYSICS, VariationModel
        if variant not in ("perturbation", "gd"):
            raise ValueError(f"unknown ode-jax variant {variant!r}")
        if n_chips < 1:
            raise ValueError(f"n_chips must be >= 1, got {n_chips}")
        self.variant = variant
        self.params = params if params is not None else DEFAULT_PHYSICS
        self.variation = (variation if variation is not None
                          else VariationModel())
        self.n_chips = n_chips
        self.chip_seed = chip_seed
        self.warmup = warmup

    def solve(self, suite, runs: int = 64, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        import dataclasses as dc

        import jax

        from ..core.device_model import DeviceModel
        from ..core.lfsr import lfsr_voltage_inits
        from ..core.perturbation import DEFAULT_PERTURBATION, NOMINAL
        from ..physics import fleet_anneal

        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        dev = DeviceModel()
        if budget is not None:
            # budget scales the anneal length — the engine's mapping
            dev = dc.replace(dev, anneal_sweeps=dev.anneal_sweeps *
                             budget_factor(budget))
        pert = DEFAULT_PERTURBATION
        if self.variant == "gd":
            dev = dc.replace(dev, tau_leak_sweeps=float("inf"))
            pert = NOMINAL
        fleet = self.n_chips > 1 or not self.variation.is_zero

        def run_bucket(bucket, b_idx):
            P, n_pad, _ = bucket.J.shape
            # the engine's exact v0 streams (machine.solve) for parity
            s0 = seed + 7919 * b_idx
            v0 = np.stack([
                lfsr_voltage_inits(n_pad, runs, seed=s0 + 7919 * p,
                                   vdd=dev.vdd, swing=dev.init_swing)
                for p in range(P)])
            chips = None
            if fleet:
                chips = self.variation.sample(self.chip_seed + b_idx,
                                              self.n_chips, n_pad)
            key = (jax.random.PRNGKey(s0)
                   if self.params.noise_sigma > 0 else None)
            res = fleet_anneal(bucket.J, v0, dev, pert,
                               params=self.params, chips=chips, key=key)
            # (C, P, R, N) -> (P, C*R, N), chip-major rows per problem
            sig = np.asarray(res.sigma)
            C = sig.shape[0]
            sig = np.moveaxis(sig, 0, 1).reshape(P, C * runs, n_pad)
            # float64 energy validation against the nominal couplings
            s64 = sig.astype(np.float64)
            J64 = np.asarray(bucket.J, dtype=np.float64)
            e = -0.5 * np.einsum("pri,pij,prj->pr", s64, J64, s64)
            return e, sig

        return _bucketed_report(
            suite, self.name, runs * self.n_chips, block, run_bucket,
            meta={"variant": self.variant, "n_chips": self.n_chips,
                  "chip_seed": self.chip_seed,
                  "physics": dataclasses.asdict(self.params),
                  "variation": dataclasses.asdict(self.variation)},
            warmup=self.warmup)


@register_solver("brute-force", needs_oracle=False, exact=True,
                 device="numpy", max_n=BRUTE_FORCE_MAX_N)
class BruteForceSolver:
    """Exhaustive exact minimum (``N <= BRUTE_FORCE_MAX_N`` — the same
    shared constant the oracle cache's exact tier cuts over at).
    ``runs``/``budget`` ignored — energies has one entry per problem, and
    it is the ground truth."""

    def solve(self, suite, runs: int = 1, seed: int = 0,
              budget: Optional[float] = None,
              block: int = CHIP_BLOCK) -> SolveReport:
        from ..solvers.brute_force import brute_force_ground_state
        suite = as_suite(suite)
        _check_max_n(suite, self.caps, self.name, block)
        energies, sigmas = [], []
        t0 = time.time()
        for p in suite:
            e, s = brute_force_ground_state(p.J_levels)
            energies.append(np.array([e], dtype=np.float64))
            sigmas.append(np.asarray(s, dtype=np.int8))
        return SolveReport(
            solver=self.name, runs=1, energies=energies, best_sigma=sigmas,
            problem_hashes=suite.hashes, sizes=suite.sizes,
            scales=tuple(p.scale for p in suite),
            wall_s=time.time() - t0, dispatches=0,
            meta={"host_evals": len(suite)})
