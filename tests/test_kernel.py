"""Pallas fused-anneal kernel vs the pure-jnp schedule-table oracle
(interpret mode).

The kernel derives the perturbation/leakage schedule IN-KERNEL from the
step index; the oracle consumes a precomputed ``schedule_table``. Voltages
agree to ~1 ULP (bit-exact for unit schedules — the leak decay's `exp` can
constant-fold differently between the two compile contexts), spins are
bit-identical. Padding paths (N not a lane multiple, R not a block
multiple) are covered explicitly; deeper parameterized parity lives in
test_engine.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.core import DeviceModel, PerturbationConfig, NOMINAL, schedule_table
from repro.core.annealer import anneal
from repro.core.lfsr import lfsr_voltage_inits
from repro.kernels import fused_anneal_kernel, fused_anneal_ref, ops
from repro.problems import problem_set


def _setup(n, p, r, seed=0, sweeps=0.5):
    dev = DeviceModel(n_spins=n, anneal_sweeps=sweeps)
    ps = problem_set(n, 0.5, p, seed=seed)
    J = np.asarray(dev.quantize(jnp.asarray(ps.J)))
    v0 = np.stack([lfsr_voltage_inits(n, r, seed=seed + i) for i in range(p)])
    return dev, J, v0


def _assert_parity(v_k, v_ref, vdd=1.0):
    v_k, v_ref = np.asarray(v_k), np.asarray(v_ref)
    np.testing.assert_allclose(v_k, v_ref, rtol=1e-5, atol=1e-5)
    assert np.array_equal(v_k >= 0.5 * vdd, v_ref >= 0.5 * vdd), \
        "spins diverged between in-kernel and table schedules"


@pytest.mark.parametrize("n,p,r", [
    (64, 1, 128),      # paper chip, exact block
    (64, 2, 130),      # run padding
    (48, 1, 64),       # lane padding (48 < 128)
    (100, 1, 32),      # both paddings
    (128, 2, 128),     # exact lane boundary
])
def test_kernel_matches_ref(n, p, r):
    dev, J, v0 = _setup(n, p, r)
    pert = PerturbationConfig()
    scales = schedule_table(dev, pert, n_cols=n)
    v_ref = fused_anneal_ref(J, v0, scales, dev.drive_eff * dev.dt, dev.vdd)
    v_k = fused_anneal_kernel(J, v0, dev=dev, pert=pert, interpret=True)
    _assert_parity(v_k, v_ref, dev.vdd)


def test_kernel_matches_annealer_end_to_end():
    dev, J, v0 = _setup(64, 2, 64, seed=4, sweeps=1.0)
    pert = PerturbationConfig()
    res = anneal(jnp.asarray(J), jnp.asarray(v0), dev, pert)
    v_k, sigma_k, e_k = ops.fused_anneal(J, v0, dev, pert, interpret=True)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(res.v_final),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(sigma_k), np.asarray(res.sigma))
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(res.energy),
                               rtol=1e-6)


def test_kernel_nominal_mode():
    dev, J, v0 = _setup(64, 1, 32, seed=9)
    scales = schedule_table(dev, NOMINAL)
    v_ref = fused_anneal_ref(J, v0, scales, dev.drive_eff * dev.dt)
    v_k = fused_anneal_kernel(J, v0, dev=dev, pert=NOMINAL, interpret=True)
    _assert_parity(v_k, v_ref)


@given(st.integers(0, 10_000))
@settings(max_examples=5, deadline=None)
def test_kernel_property_random_problems(seed):
    dev, J, v0 = _setup(32, 1, 16, seed=seed, sweeps=0.25)
    pert = PerturbationConfig(period_slots=24, off_slots=4, settle_sweeps=0.1)
    scales = schedule_table(dev, pert)
    v_ref = fused_anneal_ref(J, v0, scales, dev.drive_eff * dev.dt)
    v_k = fused_anneal_kernel(J, v0, dev=dev, pert=pert, interpret=True)
    _assert_parity(v_k, v_ref)
    assert np.all(np.asarray(v_k) >= 0) and np.all(np.asarray(v_k) <= 1)


def test_kernel_block_r_variants():
    dev, J, v0 = _setup(64, 1, 256, seed=2)
    pert = PerturbationConfig()
    outs = []
    for block_r in (64, 128, 256):
        outs.append(np.asarray(fused_anneal_kernel(
            J, v0, dev=dev, pert=pert, block_r=block_r, interpret=True)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-6)


def test_kernel_j_dtype_int8_rejects_nonunit_schedule():
    dev, J, v0 = _setup(64, 1, 32)
    with pytest.raises(ValueError):
        fused_anneal_kernel(J, v0, dev=dev, pert=PerturbationConfig(),
                            j_dtype="int8", interpret=True)


# -- two dies a tile -----------------------------------------------------------

def _packed_setup(n, p, j_dtype, r=40):
    dev, J, v0 = _setup(n, p, r, seed=n + p, sweeps=0.5)
    pert = PerturbationConfig()
    if j_dtype == "int8":        # int8 runs only under the unit schedule
        dev = dataclasses.replace(dev, tau_leak_sweeps=float("inf"))
        pert = NOMINAL
    return dev, pert, J, v0


def _pallas_calls(fn, *args) -> list:
    """(name, output shape) of each pallas_call in ``fn``'s jaxpr."""
    def walk(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], eqn.outvars[0].aval.shape))
            for prm in eqn.params.values():
                inner = getattr(prm, "jaxpr", prm)   # closed or open jaxpr
                if hasattr(inner, "eqns"):
                    found += walk(inner)
        return found
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("j_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("n", [64, 48, 33])      # < 64: die-local columns
@pytest.mark.parametrize("p", [2, 3, 5])          # odd: a half-empty tile
def test_packed_dies_equal_one_die_a_tile(j_dtype, n, p):
    """Two dies a tile give each die the v_final it gets alone in a tile,
    bit for bit; R=40 is not a multiple of block_r."""
    dev, pert, J, v0 = _packed_setup(n, p, j_dtype)
    kw = dict(dev=dev, pert=pert, block_r=16, j_dtype=j_dtype,
              interpret=True)
    packed = np.asarray(fused_anneal_kernel(J, v0, **kw))
    alone = np.concatenate([np.asarray(fused_anneal_kernel(
        J[i:i + 1], v0[i:i + 1], **kw)) for i in range(p)])
    assert packed.shape == (p, 40, n)
    assert np.array_equal(packed.view(np.uint32), alone.view(np.uint32))
    (name, shape), = _pallas_calls(
        lambda J, v: fused_anneal_kernel(J, v, **kw), J, v0)
    assert name.endswith("_x2") and shape == ((p + 1) // 2, 48, 128)


@pytest.mark.parametrize("n,p,dies", [(65, 2, 1), (64, 1, 1), (64, 2, 2),
                                      (1, 2, 2)])
def test_dies_per_tile_follows_the_shape(n, p, dies):
    from repro.kernels.ising_anneal import dies_per_tile
    assert dies_per_tile(p, n) == dies


def test_wide_die_keeps_one_die_a_tile():
    """N=65 cannot pair: the kernel is today's, one die a tile."""
    dev, pert, J, v0 = _packed_setup(65, 2, "float32", r=16)
    calls = _pallas_calls(lambda J, v: fused_anneal_kernel(
        J, v, dev=dev, pert=pert, block_r=16, interpret=True), J, v0)
    assert calls == [("fused_anneal_kernel", (2, 16, 128))]


_SHARD_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import DeviceModel, PerturbationConfig
from repro.core.lfsr import lfsr_voltage_inits
from repro.kernels import fused_anneal_kernel, ops
from repro.problems import problem_set

k, n, r = {per_shard}, 48, 24
devs = jax.devices()
assert len(devs) == 2, devs
dev, pert = DeviceModel(n_spins=n, anneal_sweeps=0.5), PerturbationConfig()
J = np.asarray(dev.quantize(jnp.asarray(problem_set(n, 0.5, 2 * k, seed=k).J)))
v0 = np.stack([lfsr_voltage_inits(n, r, seed=i) for i in range(2 * k)])
mesh = Mesh(np.asarray(devs), ("fabric",))
batch = NamedSharding(mesh, P("fabric", None, None))
Js, vs = jax.device_put(J, batch), jax.device_put(v0, batch)
assert ops.tile_dies(Js) == (2 if k >= 2 else 1)
kernel = ops._per_shard(mesh, "fabric", dev, pert, 8, "float32", True)
out = np.asarray(kernel(Js, vs))
alone = np.concatenate([np.asarray(fused_anneal_kernel(
    J[i:i + 1], v0[i:i + 1], dev=dev, pert=pert, block_r=8, interpret=True))
    for i in range(2 * k)])
assert np.array_equal(out.view(np.uint32), alone.view(np.uint32))
calls = []
def walk(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.params["name"], eqn.outvars[0].aval.shape[0]))
        for prm in eqn.params.values():
            inner = getattr(prm, "jaxpr", prm)     # closed or open jaxpr
            if hasattr(inner, "eqns"):
                walk(inner)
walk(jax.make_jaxpr(kernel)(Js, vs).jaxpr)
# each device packs its own k dies: ceil(k/2) tiles of its slice
assert calls == [("fused_anneal_kernel_x2", (k + 1) // 2)], calls
print("ok")
"""


@pytest.mark.parametrize("per_shard", [2, 3])
def test_per_shard_packs_each_slice(per_shard):
    """A batch sharded over two (host) devices is packed slice by slice
    inside ``shard_map``, with an even and an odd count per device, and
    each die still equals its one-die-a-tile anneal bit for bit."""
    import os
    import subprocess
    import sys

    import repro
    src = repro.__path__[0].rsplit("/repro", 1)[0]
    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": src,
           "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count=2"}
    out = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT.format(per_shard=per_shard)],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("ok")
