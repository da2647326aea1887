"""api.batching — the shared pad-bucket planner must be bit-identical to
the pre-refactor per-call-site bucketing it replaced.

``_legacy_buckets`` / ``_legacy_trim`` below are verbatim copies of the
pre-refactor ``ProblemSuite.buckets`` grouping/stacking and the registry's
``_bucketed_report`` trim/reorder loop — the frozen reference the planner
is pinned against (bucket membership, padded J bytes, trimmed
energies/spins), across random heterogeneous suites and every registered
solver.
"""
import numpy as np
import pytest

from hyp_compat import given, settings, st
from repro.api import (Problem, ProblemSuite, list_solvers, get_solver,
                       pad_stack, padded_size, plan_buckets)


# -- frozen pre-refactor reference -------------------------------------------

def _legacy_buckets(problems, block):
    """Verbatim pre-refactor ProblemSuite.buckets (PR 2..4 lineage)."""
    groups = {}
    for i, p in enumerate(problems):
        groups.setdefault(padded_size(p.n, block), []).append(i)
    out = []
    for n_pad in sorted(groups):
        idx = groups[n_pad]
        J = np.zeros((len(idx), n_pad, n_pad), dtype=np.float32)
        for k, i in enumerate(idx):
            n = problems[i].n
            J[k, :n, :n] = problems[i].J_levels
        out.append((n_pad, tuple(idx), J))
    return out


def _legacy_trim(problems, legacy, run_bucket):
    """Verbatim pre-refactor _bucketed_report trim/reorder inner loop."""
    energies = [None] * len(problems)
    sigmas = [None] * len(problems)
    for b_idx, (n_pad, indices, J) in enumerate(legacy):
        e, s = run_bucket(J, b_idx)
        e = np.asarray(e, dtype=np.float64)
        s = np.asarray(s)
        for k, i in enumerate(indices):
            n = problems[i].n
            best = int(np.argmin(e[k]))
            energies[i] = e[k]
            sigmas[i] = s[k, best, :n].astype(np.int8)
    return energies, sigmas


def _random_suite(seed, count, block):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(2, 3 * block + 1, size=count)
    return ProblemSuite([
        Problem.random_qubo(int(n), float(rng.uniform(0.2, 0.9)),
                            seed=seed + 31 * i)
        for i, n in enumerate(sizes)])


def _fake_run_bucket(J, b_idx):
    """Deterministic stand-in solver: content-derived (P, R) energies and
    (P, R, n_pad) spins, so trim/argmin selection paths are exercised
    without a real device dispatch."""
    P, n_pad, _ = J.shape
    R = 3
    rng = np.random.default_rng(1000 + b_idx)
    e = np.round(rng.standard_normal((P, R)) * 10
                 + J.sum(axis=(1, 2))[:, None], 3)
    s = np.where(rng.standard_normal((P, R, n_pad)) > 0, 1, -1).astype(np.int8)
    return e, s


# -- property: planner == frozen reference -----------------------------------

@settings(max_examples=16, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=4, max_value=80))
def test_plan_buckets_bit_identical_to_legacy(seed, block):
    count = 1 + seed % 7                  # heterogeneous suite sizes
    suite = _random_suite(seed, count, block)
    legacy = _legacy_buckets(suite.problems, block)

    plan = plan_buckets(suite.sizes, block)
    buckets = suite.buckets(block)
    assert [(b.n_pad, b.indices) for b in buckets] == \
        [(n_pad, idx) for n_pad, idx, _ in legacy]
    assert plan.groups == tuple((n_pad, idx) for n_pad, idx, _ in legacy)
    for b, (_, _, J) in zip(buckets, legacy):
        assert b.J.dtype == J.dtype == np.float32
        assert b.J.shape == J.shape
        assert b.J.tobytes() == J.tobytes()          # bit-identical padding

    # trimmed energies/spins: planner scatter == legacy reorder loop
    e_new, s_new = plan.scatter(
        [_fake_run_bucket(b.J, i) for i, b in enumerate(buckets)])
    e_old, s_old = _legacy_trim(suite.problems, legacy, _fake_run_bucket)
    for a, b_ in zip(e_new, e_old):
        np.testing.assert_array_equal(a, b_)
    for a, b_ in zip(s_new, s_old):
        assert a.dtype == b_.dtype == np.int8
        np.testing.assert_array_equal(a, b_)


def test_every_registered_solver_rides_the_shared_planner():
    """Post-refactor, each solver's report must still be consistent with
    the plan: jax solvers take one dispatch per planned bucket, and every
    trimmed best_sigma reproduces its reported level-space energy."""
    suite = ProblemSuite([Problem.random_qubo(n, 0.5, seed=n)
                          for n in (5, 9, 16, 12)])
    plan = plan_buckets(suite.sizes, 16)
    assert plan.num_buckets == 1
    for name, caps in list_solvers().items():
        rep = get_solver(name).solve(suite, runs=6, seed=2, block=16)
        if caps.device == "jax":
            assert rep.dispatches == plan.num_buckets, name
        for i, p in enumerate(suite):
            s = rep.best_sigma[i].astype(np.float64)
            assert s.shape == (p.n,), name
            e = -0.5 * s @ p.J_levels.astype(np.float64) @ s
            assert np.isclose(e, rep.best_energy[i]), name


# -- pad_stack contract ------------------------------------------------------

def test_pad_stack_shapes_and_zero_padding():
    a = np.full((3, 3), 2.0)
    b = np.full((2, 5, 5), -1.0)                     # pre-batched (R, m, m)
    out = pad_stack([a, b], 8)
    assert out.shape == (3, 8, 8) and out.dtype == np.float32
    assert np.all(out[0, :3, :3] == 2.0) and np.all(out[0, 3:, :] == 0)
    assert np.all(out[1:, :5, :5] == -1.0) and np.all(out[1:, :, 5:] == 0)
    with pytest.raises(ValueError, match="cannot pad"):
        pad_stack([np.zeros((9, 9))], 8)
    with pytest.raises(ValueError, match="square"):
        pad_stack([np.zeros((2, 3))], 8)


def test_chip_lns_stacking_unchanged_by_pad_stack_route():
    """chip-lns (one color on one die of the fabric's LNS) builds its
    sub-instance batch from a prebuilt template — deterministic
    end-to-end parity pins the route."""
    suite = ProblemSuite([Problem.random_qubo(70, 0.4, seed=11)])
    kw = dict(inner_runs=2, outer_sweeps=2, anneal_sweeps=0.37)
    r1 = get_solver("chip-lns", **kw).solve(suite, runs=2, seed=5)
    r2 = get_solver("chip-lns", **kw).solve(suite, runs=2, seed=5)
    np.testing.assert_array_equal(r1.best_energy, r2.best_energy)
    np.testing.assert_array_equal(r1.best_sigma[0], r2.best_sigma[0])
    # monotone vs init (the LNS acceptance contract, unchanged)
    assert np.all(np.asarray(r1.energies[0]) <=
                  np.asarray(r1.meta["init_energies"][0]) + 1e-9)
