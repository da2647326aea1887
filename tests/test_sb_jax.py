"""Simulated bifurcation (sb-jax): kernel parity, padding, metrology, and
the shared sign(0) -> +1 binarization convention."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Problem, ProblemSuite, get_solver
from repro.core.binarize import sign_pm1
from repro.core.device_model import DeviceModel
from repro.kernels.sb_kernel import (SB_VARIANTS, fused_sb_kernel,
                                     sb_reference)
from repro.solvers import simulated_bifurcation_jax_runs
from repro.solvers.brute_force import brute_force_ground_state
from repro.solvers.sb_jax import sb_coupling_scale


def _random_ising(n, seed, P=1):
    rng = np.random.default_rng(seed)
    J = rng.integers(-7, 8, (P, n, n)).astype(np.float64)
    J = np.round((J + np.swapaxes(J, 1, 2)) / 2)
    for p in range(P):
        np.fill_diagonal(J[p], 0)
    return J


# -- dynamics reach the ground state -----------------------------------------

@pytest.mark.parametrize("variant", SB_VARIANTS)
def test_sb_matches_brute_force_small(variant):
    """bSB and dSB reach the ground state of every instance. aSB does not
    promise that: without inelastic walls every restart follows the same
    adiabatic bifurcation branch, so on problem 1 (ground state -88) almost
    all restarts settle at -82 whatever the init draw (1 of 128 restarts
    over 8 seeds reached -88; dt 0.1-0.5 and 400-1000 steps alike). What
    aSB does deliver, across init draws: the ground state on most
    instances and within 10% of it on all."""
    J = _random_ising(12, seed=7, P=3)
    # aSB has no inelastic walls, so its amplitude error compounds with dt
    dt = 0.25 if variant == "aSB" else 0.5
    e, s = simulated_bifurcation_jax_runs(J, variant=variant, n_steps=400,
                                          n_restarts=16, dt=dt, seed=0)
    assert e.shape == (3, 16) and s.shape == (3, 16, 12)
    assert s.dtype == np.int8 and set(np.unique(s)) <= {-1, 1}
    ground = np.array([brute_force_ground_state(J[p])[0] for p in range(3)])
    if variant == "aSB":
        assert np.isclose(e.min(axis=1), ground).sum() >= 2
        assert np.all(e.min(axis=1) <= 0.9 * ground)    # energies < 0
    else:
        np.testing.assert_allclose(e.min(axis=1), ground)
    for p in range(3):
        # reported energies are exactly the energies of the reported spins
        best = int(np.argmin(e[p]))
        sb = s[p, best].astype(np.float64)
        assert np.isclose(-0.5 * sb @ J[p] @ sb, e[p].min())


# -- fused kernel vs scan oracle ---------------------------------------------

@pytest.mark.parametrize("variant", SB_VARIANTS)
def test_sb_kernel_matches_scan_reference_bitwise(variant):
    J = _random_ising(24, seed=1, P=2) * 0.01
    rng = np.random.default_rng(2)
    x0 = rng.uniform(-0.1, 0.1, (2, 8, 24)).astype(np.float32)
    y0 = rng.uniform(-0.1, 0.1, (2, 8, 24)).astype(np.float32)
    k = fused_sb_kernel(J, x0, y0, variant=variant, n_steps=300, block_r=8)
    r = sb_reference(J, x0, y0, variant=variant, n_steps=300)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


def test_sb_kernel_rejects_unknown_variant():
    J = np.zeros((1, 8, 8), np.float32)
    z = np.zeros((1, 4, 8), np.float32)
    with pytest.raises(ValueError, match="variant"):
        fused_sb_kernel(J, z, z, variant="xSB")
    with pytest.raises(ValueError, match="variant"):
        simulated_bifurcation_jax_runs(J, variant="xSB")


# -- padded buckets ----------------------------------------------------------

def test_sb_padded_bucket_is_exact():
    """A 16-spin problem embedded in a 64-pad bucket solves the SAME
    problem: c0 comes from the true size, padded spins stay inert through
    the dynamics and read +1 at the sign_pm1 boundary."""
    n = 16
    J = _random_ising(n, seed=4)
    Jpad = np.zeros((1, 64, 64))
    Jpad[:, :n, :n] = J
    e_bf, _ = brute_force_ground_state(J[0])
    e, s = simulated_bifurcation_jax_runs(Jpad, n_true=[n], variant="bSB",
                                          n_steps=400, n_restarts=16, seed=5)
    assert np.all(s[:, :, n:] == 1)          # pads pinned at the +1 readout
    assert np.isclose(e.min(), e_bf)
    # padding never perturbs the normalization the dynamics run at
    assert np.isclose(sb_coupling_scale(Jpad, [n])[0],
                      sb_coupling_scale(J)[0])


def test_sb_coupling_scale_degenerate_problems():
    c0 = sb_coupling_scale(np.zeros((2, 8, 8)), [8, 1])
    assert np.all(c0 == 1.0)                 # all-zero J / single spin: finite


# -- registry metrology ------------------------------------------------------

def test_sb_registry_one_dispatch_per_bucket():
    suite = ProblemSuite([Problem.random_qubo(16, 0.5, seed=1),
                          Problem.random_qubo(40, 0.5, seed=2),
                          Problem.random_qubo(64, 0.5, seed=3),
                          Problem.random_qubo(70, 0.5, seed=4)])
    assert suite.num_dispatches() == 2       # {16,40,64} -> 64; {70} -> 128
    rep = get_solver("sb-jax").solve(suite, runs=8, seed=0)
    assert rep.dispatches == suite.num_dispatches()
    assert rep.solver == "sb-jax" and rep.meta["variant"] == "bSB"
    for i, p in enumerate(suite):
        s = rep.best_sigma[i].astype(np.float64)
        assert s.shape == (p.n,)
        e = -0.5 * s @ p.J_levels.astype(np.float64) @ s
        assert np.isclose(e, rep.best_energy[i])


def test_sb_determinism_same_seed_bit_identical():
    suite = ProblemSuite.random(24, 0.5, 2, seed=11)
    r1 = get_solver("sb-jax").solve(suite, runs=8, seed=3)
    r2 = get_solver("sb-jax").solve(suite, runs=8, seed=3)
    for a, b in zip(r1.energies, r2.energies):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(r1.best_sigma, r2.best_sigma):
        np.testing.assert_array_equal(a, b)
    r3 = get_solver("sb-jax").solve(suite, runs=8, seed=4)
    assert any(not np.array_equal(a, b)
               for a, b in zip(r1.energies, r3.energies))


def test_sb_budget_scales_iters_not_restarts():
    suite = ProblemSuite.random(16, 0.5, 1, seed=6)
    base = get_solver("sb-jax", n_steps=64).solve(suite, runs=8, seed=0)
    double = get_solver("sb-jax", n_steps=64).solve(suite, runs=8, seed=0,
                                                    budget=2.0)
    assert base.meta["effort"]["iters"] == 64
    assert double.meta["effort"]["iters"] == 128
    assert base.meta["effort"]["restarts"] == \
        double.meta["effort"]["restarts"] == 8


def test_sb_warmup_splits_compile_from_wall():
    suite = ProblemSuite.random(16, 0.5, 1, seed=8)
    rep = get_solver("sb-jax", warmup=True, n_steps=64).solve(
        suite, runs=8, seed=0)
    assert rep.wall_s > 0 and rep.compile_s >= 0
    rep2 = get_solver("sb-jax", n_steps=64).solve(suite, runs=8, seed=0)
    for a, b in zip(rep.energies, rep2.energies):    # warmup never reroots
        np.testing.assert_array_equal(a, b)          # the deterministic seed


def test_sb_rejects_bad_variant_at_registry():
    with pytest.raises(ValueError, match="variant"):
        get_solver("sb-jax", variant="zSB")


# -- the one sign(0) -> +1 convention ----------------------------------------

def test_sign_pm1_boundary_and_dtypes():
    x = np.array([-1.0, -1e-30, -0.0, 0.0, 1e-30, 1.0], np.float32)
    out = np.asarray(sign_pm1(x))
    # the decision boundary maps to +1 on BOTH float zeros (-0.0 >= 0);
    # anything strictly negative — however tiny — stays -1
    np.testing.assert_array_equal(out, [-1, -1, 1, 1, 1, 1])
    assert out.dtype == np.float32
    assert np.asarray(sign_pm1(x, dtype=jnp.int8)).dtype == np.int8
    # jnp.sign would emit 0 here — the convention exists to forbid that
    assert np.asarray(jnp.sign(0.0)) == 0.0


def test_sign_convention_agrees_across_all_three_paths():
    """Property test: engine ADC, ode-jax hard-gain limit, and SB readout
    binarize ANY voltage identically — including states parked exactly on
    the decision boundary."""
    from repro.physics import DISCRETE_LIMIT
    from repro.physics.dynamics import _node_output

    dev = DeviceModel()
    rng = np.random.default_rng(13)
    v = rng.uniform(0.0, dev.vdd, 256).astype(np.float32)
    v[:4] = [dev.threshold, np.nextafter(np.float32(dev.threshold),
                                         np.float32(0.0)), 0.0, dev.vdd]
    adc = np.asarray(dev.adc(v))
    ode = np.asarray(_node_output(jnp.asarray(v), dev, DISCRETE_LIMIT, None))
    sb = np.asarray(sign_pm1(v - dev.threshold))     # SB reads out around 0
    np.testing.assert_array_equal(adc, ode)
    np.testing.assert_array_equal(np.sign(adc), np.sign(sb))
    assert adc[0] == 1.0 and adc[1] == -1.0          # boundary -> +1, below -> -1
