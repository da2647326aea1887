"""The unit-schedule reference against the program on the CPU: it equals
the int8 fused kernel (interpret mode) and the scan path bit for bit, and
differs from the perturbation-schedule reference, so the check of a cell
without perturbation can tell the two arms apart."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import anneal, anneal_unit, conventions, lfsr


def _inputs(n, problems=2, runs=8, seed=5):
    rng = np.random.default_rng(seed)
    iu = np.triu_indices(n, k=1)
    J = np.zeros((problems, n, n), np.float32)
    for p in range(problems):
        w = rng.integers(-15, 16, size=len(iu[0])) * (rng.random(len(iu[0]))
                                                      < 0.5)
        J[p][iu] = w
        J[p] += J[p].T
    v0 = lfsr.voltages(conventions.engine_seeds(11, range(problems)), n,
                       runs, swing=conventions.ENGINE_SWING)
    return J, v0


@pytest.mark.parametrize("n", [24, 64])
def test_unit_reference_is_the_programs_gd_anneal(n):
    from repro.core import NOMINAL, DeviceModel, anneal as scan
    from repro.kernels.ising_anneal import fused_anneal_kernel
    J, v0 = _inputs(n)
    dev = dataclasses.replace(DeviceModel(n_spins=n),
                              tau_leak_sweeps=float("inf"))
    assert dev.n_steps == anneal.N_STEPS
    v_ref, s_ref = anneal_unit.anneal(jnp.asarray(J), jnp.asarray(v0))
    v_int8 = fused_anneal_kernel(J, v0, dev=dev, pert=NOMINAL, block_r=8,
                                 j_dtype="int8", interpret=True)
    v_scan = scan(jnp.asarray(J), jnp.asarray(v0), dev, NOMINAL).v_final
    np.testing.assert_array_equal(np.asarray(v_int8), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(v_scan), np.asarray(v_ref))
    # the perturbation arm anneals other runs from the same inits
    s_pert, _ = anneal.run(J, v0, precision="bfloat16")
    assert not np.array_equal(s_pert, np.asarray(s_ref))
