"""Continuous-time dynamics invariants (paper Eq. 6): pure gradient descent
is energy-non-increasing; anneals are deterministic; final states are
1-flip-stable local minima."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hyp_compat import given, settings, st

from repro.core import (DeviceModel, IsingMachine, NOMINAL,
                        PerturbationConfig, anneal, flip_deltas,
                        ising_energy)
from repro.core.lfsr import lfsr_voltage_inits
from repro.problems import problem_set


def _gd_device(n, sweeps=3.75):
    return DeviceModel(n_spins=n, anneal_sweeps=sweeps,
                       tau_leak_sweeps=float("inf"), noise_sigma=0.0)


def _gd_replay(J, v0, dev):
    """The unit-schedule Euler anneal replayed in numpy float32, one step at
    a time: (spins before each step, spins after it), each (T, R, N)."""
    dd = np.float32(dev.drive_eff * dev.dt)
    v = v0.astype(np.float32)
    Jf = J.astype(np.float32)
    before, after = [], []
    for _ in range(dev.n_steps):
        q = np.where(v >= dev.threshold, 1.0, -1.0).astype(np.float32)
        v = np.clip(v + (q @ Jf) * dd, np.float32(0.0),
                    np.float32(dev.vdd))
        before.append(q)
        after.append(np.where(v >= dev.threshold, 1.0, -1.0))
    return np.array(before, np.float64), np.array(after, np.float64), v


def _energies(s, J):
    return -0.5 * np.einsum("tri,ij,trj->tr", s, J, s)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_gd_energy_monotone_in_fine_dt_limit(seed):
    """Eq. (6) holds in CONTINUOUS time for one spin crossing at a time;
    the Euler discretization can raise H when several coupled spins cross
    threshold in the same step, since none of them sees the others' flip.
    Flipping the set F of spins s changes H by exactly
    2 * sum_{i in F} s_i h_i - 2 * sum_{i, j in F} J_ij s_i s_j, with h the
    fields before the step.

    What the anneal guarantees is per step, at every dt: each spin that
    flips moves along its own field (s_i h_i < 0 before the flip), so a
    step that flips one spin lowers H by 2 |h_i| >= 2, and H rises only in
    a step that flips several at once. It does NOT guarantee that the
    mass of those rises shrinks as dt does. The LFSR init puts every
    voltage at the same distance from threshold and the fields are
    integers, so spins under equal opposing fields cross at the same
    instant even in continuous time, at any dt; and a finer dt follows a
    different path, which can meet such a tie that a coarser one missed
    (seed 55639: rise mass 0, 0.0087, 0.0284 at 2, 8, 32 substeps)."""
    n = 24
    ps = problem_set(n, 0.5, 1, seed=seed % 100000)
    J = np.asarray(ps.J)[0].astype(np.float64)
    v0 = lfsr_voltage_inits(n, 4, seed=seed % 999)
    for substeps in (2, 8, 32):
        dev = dataclasses.replace(_gd_device(n, sweeps=2.0),
                                  substeps=substeps)
        res = anneal(jnp.asarray(ps.J), jnp.asarray(v0[None]), dev, NOMINAL,
                     record_every=1)
        traj = np.asarray(res.energy_traj)[0].T            # (T, R)
        before, after, v_final = _gd_replay(J, v0, dev)
        # the replay is the program's anneal, bit for bit
        np.testing.assert_array_equal(v_final, np.asarray(res.v_final)[0])
        np.testing.assert_array_equal(_energies(after, J), traj)
        flipped = before != after
        h = before @ J
        # every flip moves a spin along its own field
        assert np.all(before * h < 0, where=flipped)
        n_flips = flipped.sum(axis=-1)
        dH = _energies(after, J) - _energies(before, J)
        one = n_flips == 1
        np.testing.assert_array_equal(
            dH[one], -2 * np.abs(h * flipped).sum(axis=-1)[one])
        # a rise needs several spins flipping in the same step
        assert np.all(n_flips[dH > 0] >= 2), substeps
        # descent always dominates: final well below initial
        assert traj[-1].mean() < traj[0].mean()


def test_gd_reaches_local_minima():
    n = 32
    ps = problem_set(n, 0.5, 2, seed=11)
    dev = _gd_device(n, sweeps=6.0)
    m = IsingMachine(device=dev, perturbation=NOMINAL)
    out = m.solve(ps.J, num_runs=32, seed=1)
    dH = np.asarray(flip_deltas(jnp.asarray(ps.J), out.sigma))
    frac_locmin = (dH >= -1e-6).all(axis=-1).mean()
    assert frac_locmin > 0.9


def test_anneal_deterministic():
    ps = problem_set(16, 0.5, 1, seed=5)
    m = IsingMachine()
    a = m.solve(ps.J, num_runs=8, seed=3)
    b = m.solve(ps.J, num_runs=8, seed=3)
    assert np.array_equal(a.sigma, b.sigma)
    c = m.solve(ps.J, num_runs=8, seed=4)
    assert not np.array_equal(a.v_final, c.v_final)


def test_voltages_bounded():
    ps = problem_set(16, 0.9, 1, seed=6)
    m = IsingMachine()
    out = m.solve(ps.J, num_runs=8, seed=2)
    assert out.v_final.min() >= 0.0
    assert out.v_final.max() <= 1.0


def test_noise_path_changes_outcome():
    ps = problem_set(16, 0.5, 1, seed=7)
    m = IsingMachine()
    noisy = m.inherent_noise_baseline(sigma=5.0)
    a = m.gradient_descent_baseline().solve(ps.J, num_runs=16, seed=3)
    b = noisy.solve(ps.J, num_runs=16, seed=3,
                    key=jax.random.PRNGKey(9))
    assert not np.array_equal(a.sigma, b.sigma)


def test_perturbation_improves_success():
    """The paper's headline claim (Fig. 4): >1.7x SR vs GD-only.
    Small sample here; the full benchmark reproduces the figure."""
    n = 48
    ps = problem_set(n, 0.5, 4, seed=21)
    from repro.solvers import best_known
    bk = best_known(ps.J, seed=2)
    m = IsingMachine()
    sr_p = m.solve(ps.J, num_runs=120, seed=5).success_rate(bk).mean()
    sr_g = (m.gradient_descent_baseline().solve(ps.J, num_runs=120, seed=5)
            .success_rate(bk).mean())
    assert sr_p > sr_g, f"perturbation SR {sr_p} not above GD {sr_g}"
