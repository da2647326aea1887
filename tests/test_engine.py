"""AnnealEngine: dispatch rules, in-kernel-schedule parity, int8 fast path,
autotune cache, and the JAX SA baseline.

Parity contract (see ENGINE.md): the fused kernel's in-kernel closed-form
schedule must produce BIT-IDENTICAL spins vs the ``schedule_table``-based
oracle in every mode; voltages are bit-exact for unit schedules and agree
to ~1 ULP when the leak-decay ``exp`` is in play (XLA constant-folds the
precomputed table's exp in a different context than the kernel's runtime
exp). Everything runs in interpret mode on CPU.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AnnealEngine, DeviceModel, DEFAULT_PERTURBATION,
                        EnginePlan, IsingMachine, NOMINAL,
                        PerturbationConfig, schedule_table, unit_scales)
from repro.core.lfsr import lfsr_voltage_inits
from repro.kernels import fused_anneal_kernel, fused_anneal_ref
from repro.problems import problem_set
from repro.solvers import (brute_force_ground_state, simulated_annealing,
                           simulated_annealing_jax)


def _setup(n, p, r, seed=0, sweeps=0.5, tau=10.0):
    dev = DeviceModel(n_spins=n, anneal_sweeps=sweeps, tau_leak_sweeps=tau)
    ps = problem_set(n, 0.5, p, seed=seed)
    J = np.asarray(dev.quantize(jnp.asarray(ps.J)))
    v0 = np.stack([lfsr_voltage_inits(n, r, seed=seed + i) for i in range(p)])
    return dev, J, v0


# ---------------------------------------------------------------------------
# In-kernel closed-form schedule vs schedule_table oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pert", [NOMINAL, DEFAULT_PERTURBATION],
                         ids=["nominal", "perturbation"])
@pytest.mark.parametrize("tau", [10.0, float("inf")],
                         ids=["leak", "no-leak"])
@pytest.mark.parametrize("n,p,r,block_r", [
    (64, 1, 128, 128),     # paper chip, exact block
    (48, 1, 64, 64),       # lane padding (48 < 128)
    (100, 2, 40, 64),      # padded N AND R not a multiple of block_r
    (64, 1, 96, 64),       # R not a multiple of block_r
])
def test_closed_form_schedule_parity(pert, tau, n, p, r, block_r):
    dev, J, v0 = _setup(n, p, r, tau=tau, sweeps=1.0)
    scales = schedule_table(dev, pert, n_cols=n)
    v_ref = np.asarray(fused_anneal_ref(J, v0, scales,
                                        dev.drive_eff * dev.dt, dev.vdd))
    v_k = np.asarray(fused_anneal_kernel(J, v0, dev=dev, pert=pert,
                                         block_r=block_r, interpret=True))
    # Spins: bit-identical in every mode (the acceptance contract).
    assert np.array_equal(v_k >= dev.threshold, v_ref >= dev.threshold)
    if unit_scales(dev, pert):
        # No exp in the schedule -> voltages bit-exact too.
        assert np.array_equal(v_k, v_ref)
    else:
        np.testing.assert_allclose(v_k, v_ref, rtol=2e-6, atol=2e-6)


def test_int8_fast_path_bit_exact():
    """Unit schedule + integer J: int8 MXU path must equal f32 bitwise."""
    dev, J, v0 = _setup(64, 2, 64, tau=float("inf"), sweeps=1.0)
    v_f32 = np.asarray(fused_anneal_kernel(J, v0, dev=dev, pert=NOMINAL,
                                           j_dtype="float32", interpret=True))
    v_i8 = np.asarray(fused_anneal_kernel(J, v0, dev=dev, pert=NOMINAL,
                                          j_dtype="int8", interpret=True))
    assert np.array_equal(v_f32, v_i8)


def test_int8_rejects_non_integer_levels():
    from repro.kernels import ops
    dev, J, v0 = _setup(32, 1, 8, tau=float("inf"))
    with pytest.raises(ValueError, match="integer coupling"):
        ops.fused_anneal(J + 0.5, v0, dev, NOMINAL, j_dtype="int8",
                         interpret=True)


def test_bf16_j_exact_for_unit_schedule():
    dev, J, v0 = _setup(48, 1, 32, tau=float("inf"), sweeps=0.5)
    v_f32 = np.asarray(fused_anneal_kernel(J, v0, dev=dev, pert=NOMINAL,
                                           j_dtype="float32", interpret=True))
    v_bf = np.asarray(fused_anneal_kernel(J, v0, dev=dev, pert=NOMINAL,
                                          j_dtype="bfloat16", interpret=True))
    # integer levels and power-of-two drive_dt are exact in bf16
    assert np.array_equal(v_f32, v_bf)


# ---------------------------------------------------------------------------
# Engine dispatch
# ---------------------------------------------------------------------------
def test_engine_auto_plan_cpu_is_scan(tmp_path):
    eng = AnnealEngine(cache_path=str(tmp_path / "cache.json"))
    plan = eng.plan(2, 128, 64)
    assert isinstance(plan, EnginePlan)
    assert plan.path == "scan" and plan.reason == "auto"
    assert plan.interpret  # off-TPU


def test_engine_feature_fallback_forces_scan(tmp_path):
    eng = AnnealEngine(path="fused",
                       cache_path=str(tmp_path / "cache.json"))
    plan = eng.plan(1, 8, 16, needs_scan=True)
    assert plan.path == "scan" and plan.reason.startswith("feature")
    # and record_every actually yields a trajectory through the fused engine
    dev, J, v0 = _setup(16, 1, 8)
    eng = AnnealEngine(device=dev, path="fused",
                       cache_path=str(tmp_path / "cache.json"))
    res = eng.run(J, v0, record_every=2)
    assert res.energy_traj is not None


def test_engine_fused_matches_scan(tmp_path):
    dev, J, v0 = _setup(64, 1, 64, sweeps=1.0)
    scan_res = AnnealEngine(device=dev, path="scan",
                            cache_path=str(tmp_path / "c.json")).run(J, v0)
    fused_res = AnnealEngine(device=dev, path="fused",
                             cache_path=str(tmp_path / "c.json")).run(J, v0)
    assert np.array_equal(np.asarray(scan_res.sigma),
                          np.asarray(fused_res.sigma))
    np.testing.assert_allclose(np.asarray(scan_res.v_final),
                               np.asarray(fused_res.v_final),
                               rtol=1e-5, atol=1e-5)


def test_engine_int8_autoselect_gd_baseline(tmp_path):
    dev = DeviceModel(n_spins=32, tau_leak_sweeps=float("inf"))
    eng = AnnealEngine(device=dev, perturbation=NOMINAL,
                       cache_path=str(tmp_path / "c.json"))
    _, J, _ = _setup(32, 1, 8, tau=float("inf"))
    plan = eng.plan(1, 8, 32, J=J)
    assert plan.j_dtype == "int8"
    # non-integer J falls back to float
    plan_f = eng.plan(1, 8, 32, J=J + 0.25)
    assert plan_f.j_dtype == "float32"


def test_engine_autotune_cache_roundtrip(tmp_path):
    cache = str(tmp_path / "autotune.json")
    dev = DeviceModel(n_spins=32, anneal_sweeps=0.25)
    eng = AnnealEngine(device=dev, cache_path=cache)
    plan = eng.autotune(1, 32, 32, probe_sweeps=0.125,
                        candidates=(16, 32))
    assert plan.reason == "autotuned"
    assert (tmp_path / "autotune.json").exists()
    # a fresh engine picks the tuned plan straight from the cache
    eng2 = AnnealEngine(device=dev, cache_path=cache)
    plan2 = eng2.plan(1, 32, 32)
    assert plan2.reason == "cache"
    assert plan2.path == plan.path and plan2.block_r == plan.block_r


def _fake_tpu_autotune(monkeypatch, fused):
    """Drive the autotuner's fused branch off-TPU with a stand-in kernel."""
    from repro.core import engine as engine_mod
    from repro.kernels import ops
    monkeypatch.setattr(engine_mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "fused_anneal", fused)


def test_autotune_skips_vmem_overflow_and_names_it(tmp_path, monkeypatch):
    def fused(*a, block_r, **kw):
        if block_r == 32:
            raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in "
                               "memory space vmem while allocating")
        return jnp.zeros(())
    _fake_tpu_autotune(monkeypatch, fused)
    cache = str(tmp_path / "autotune.json")
    dev = DeviceModel(n_spins=16, anneal_sweeps=0.25)
    plan = AnnealEngine(device=dev, cache_path=cache).autotune(
        1, 32, 16, probe_sweeps=0.125, candidates=(16, 32))
    assert plan.reason == "autotuned; skipped fused block_r=32 (VMEM overflow)"
    # the skip survives in the cache and in every plan read from it
    plan2 = AnnealEngine(device=dev, cache_path=cache).plan(1, 32, 16)
    assert plan2.reason == "cache; skipped fused block_r=32 (VMEM overflow)"


def test_autotune_reraises_fused_faults(tmp_path, monkeypatch):
    def fused(*a, **kw):
        raise ValueError("Mosaic failed to compile TPU kernel")
    _fake_tpu_autotune(monkeypatch, fused)
    eng = AnnealEngine(device=DeviceModel(n_spins=16, anneal_sweeps=0.25),
                       cache_path=str(tmp_path / "autotune.json"))
    with pytest.raises(ValueError, match="Mosaic"):
        eng.autotune(1, 32, 16, probe_sweeps=0.125, candidates=(16, 32))
    assert not (tmp_path / "autotune.json").exists()   # nothing tuned


def test_machine_backends_agree_via_engine():
    ps = problem_set(48, 0.5, 1, seed=5)
    a = IsingMachine(backend="jnp").solve(ps.J, num_runs=32, seed=3)
    b = IsingMachine(backend="pallas").solve(ps.J, num_runs=32, seed=3)
    assert np.array_equal(a.sigma, b.sigma)
    np.testing.assert_allclose(a.energy, b.energy, rtol=1e-6)


# ---------------------------------------------------------------------------
# chip-lns: multi-chip decomposition past the single-die limit
# ---------------------------------------------------------------------------
def test_chip_lns_small_n_matches_direct_engine_solve():
    """N <= 64 delegates verbatim: bit-identical per-run energies."""
    from repro.api import ProblemSuite, get_solver
    suite = ProblemSuite.random(32, 0.5, 2, seed=4)
    rep_e = get_solver("engine").solve(suite, runs=16, seed=3)
    rep_l = get_solver("chip-lns").solve(suite, runs=16, seed=3)
    for a, b in zip(rep_e.energies, rep_l.energies):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(rep_e.best_sigma, rep_l.best_sigma):
        np.testing.assert_array_equal(a, b)


def test_chip_lns_beyond_die_deterministic_and_monotone():
    """N = 96/128: deterministic per seed, never worse than its own
    initialization, one device dispatch per outer sweep."""
    from repro.api import Problem, ProblemSuite, get_solver

    suite = ProblemSuite([Problem.maxcut(96, 0.3, seed=1),
                          Problem.random_qubo(128, 0.2, seed=2)])
    opts = dict(inner_runs=4, anneal_sweeps=1.0)
    rep = get_solver("chip-lns", **opts).solve(suite, runs=4, seed=5,
                                               budget=0.5)
    rep2 = get_solver("chip-lns", **opts).solve(suite, runs=4, seed=5,
                                                budget=0.5)
    for a, b in zip(rep.energies, rep2.energies):
        np.testing.assert_array_equal(a, b)          # deterministic per seed
    assert rep.dispatches == rep.meta["outer_sweeps"]
    for i, p in enumerate(suite):
        init = np.asarray(rep.meta["init_energies"][i])
        final = np.asarray(rep.energies[i])
        assert final.shape == init.shape == (4,)
        assert np.all(final <= init + 1e-9)          # monotone acceptance
        assert final.min() < init.min()              # and it actually moved
        # trimmed best_sigma attains the reported energy on the full J
        s = rep.best_sigma[i].astype(np.float64)
        e = -0.5 * s @ p.J_levels.astype(np.float64) @ s
        assert np.isclose(e, rep.best_energy[i])
    # a different seed explores a different trajectory
    rep3 = get_solver("chip-lns", **opts).solve(suite, runs=4, seed=6,
                                                budget=0.5)
    assert any(not np.array_equal(a, b)
               for a, b in zip(rep.energies, rep3.energies))


def test_chip_lns_answers_pinned():
    """chip-lns returns, bit for bit, the per-run energies and best spins
    it returned as a one-color dispatch per sweep of its own search (the
    suite and options of the test above)."""
    from repro.api import Problem, ProblemSuite, get_solver

    suite = ProblemSuite([Problem.maxcut(96, 0.3, seed=1),
                          Problem.random_qubo(128, 0.2, seed=2)])
    rep = get_solver("chip-lns", inner_runs=4, anneal_sweeps=1.0).solve(
        suite, runs=4, seed=5, budget=0.5)
    want_e = [[-2853.0, -3093.0, -2733.0, -2871.0],
              [-3788.0, -3296.0, -3372.0, -3736.0]]
    want_s = ["+++++--+-+-+--++++---+-++++++-+-------++++-++---+--+-----++--"
              "++-+---+++--+++-+++--+++-++---++--+",
              "-++++-++-+++--+-+++++++-+-----+-----++-+++++-+---+-+--------"
              "-+--++-+-+--+++-+---++++-+-+++----++++-++++--+-+----++--+--+"
              "-+---+-+"]
    for e, s, we, ws in zip(rep.energies, rep.best_sigma, want_e, want_s):
        np.testing.assert_array_equal(np.asarray(e, np.float64), we)
        assert s.dtype == np.int8
        assert "".join("+" if x > 0 else "-" for x in s) == ws
    assert rep.meta["init_energies"] == {0: [93.0, 237.0, 57.0, 473.0],
                                         1: [-32.0, 186.0, 46.0, 336.0]}


def test_single_die_solvers_reject_padded_virtual_chips():
    """The capability check fires BEFORE bucketing pads N=96 to a 128-spin
    virtual chip nobody manufactured."""
    from repro.api import Problem, ProblemSuite, get_solver
    suite = ProblemSuite([Problem.maxcut(96, 0.3, seed=1)])
    with pytest.raises(ValueError, match="chip-lns"):
        get_solver("engine").solve(suite, runs=4, seed=0)
    with pytest.raises(ValueError, match="max_n"):
        get_solver("brute-force").solve(suite)
    # capacity-free solvers still take it
    rep = get_solver("tabu").solve(suite, runs=2, seed=0, budget=0.1)
    assert rep.num_problems == 1


def test_lns_blocks_partition():
    from repro.distributed.fabric import lns_blocks
    blocks = lns_blocks(128, 63)
    assert sum(len(b) for b in blocks) == 128
    assert max(len(b) for b in blocks) <= 63
    np.testing.assert_array_equal(np.concatenate(blocks), np.arange(128))
    assert len(lns_blocks(64, 63)) == 2 and len(lns_blocks(63, 63)) == 1


# ---------------------------------------------------------------------------
# JAX SA baseline
# ---------------------------------------------------------------------------
def test_sa_jax_matches_numpy_and_brute_force():
    dev = DeviceModel()
    ps = problem_set(16, 0.5, 2, seed=3)
    for p in range(2):
        J = np.asarray(dev.quantize(jnp.asarray(ps.J[p])))
        e_np, _ = simulated_annealing(J, n_sweeps=150, n_restarts=32, seed=1)
        e_jx, s_jx = simulated_annealing_jax(J, n_sweeps=150, n_restarts=32,
                                             seed=1)
        e_bf, _ = brute_force_ground_state(J)
        assert e_np == e_jx == pytest.approx(e_bf)
        # returned sigma actually attains the returned energy
        f = J @ s_jx.astype(np.float64)
        assert -0.5 * float(s_jx @ f) == pytest.approx(e_jx)


def test_sa_jax_batched_problems():
    dev = DeviceModel()
    ps = problem_set(32, 0.5, 3, seed=9)
    Jq = np.asarray(dev.quantize(jnp.asarray(ps.J)))
    e_np = np.array([simulated_annealing(Jq[p], n_sweeps=300, n_restarts=64,
                                         seed=p)[0] for p in range(3)])
    e_jx, s_jx = simulated_annealing_jax(Jq, n_sweeps=300, n_restarts=64,
                                         seed=0)
    assert e_jx.shape == (3,) and s_jx.shape == (3, 32)
    np.testing.assert_allclose(e_jx, e_np)
