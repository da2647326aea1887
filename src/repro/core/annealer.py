"""Batched continuous-time anneal (paper Eq. 3-6) — pure-JAX reference path.

The dynamics integrated here are the chip's node equation

    dv_i/dt = (a/C) * sum_j  s_j(t) * J_ij * Q(v_j),     v clipped to [0, VDD]

with s(t) the deterministic column-scale schedule from ``perturbation.py``
(leakage + landscape perturbation folded into one per-column scalar; see
DESIGN.md §2). With s == 1 this is exact gradient descent on the Ising
Hamiltonian and the energy is non-increasing (Eq. 6) — a property test pins
that invariant.

Shapes: J (P, N, N) integer coupling levels; v0 (P, R, N) voltages
(P problems, R runs per problem). All axes are batch-shardable.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .binarize import sign_pm1
from .device_model import DeviceModel
from .perturbation import PerturbationConfig, column_scales
from .hamiltonian import ising_energy


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AnnealResult:
    v_final: jax.Array          # (P, R, N) final capacitor voltages
    sigma: jax.Array            # (P, R, N) final spins (+-1)
    energy: jax.Array           # (P, R) final Ising energy (unscaled J)
    energy_traj: Optional[jax.Array] = None   # (P, R, T_rec) if recorded


def _step(v, t, J, dev: DeviceModel, pert: PerturbationConfig, noise=None):
    # drive_dt folded into the per-column scales OUTSIDE the matvec (the
    # same grouping as the fused kernel and ref oracle, keeping the three
    # paths bit-identical in f32; for power-of-two drive_dt — the default —
    # the fold is an exact exponent shift, so results are unchanged).
    s = column_scales(t, dev, pert, n_cols=J.shape[-1]) \
        * (dev.drive_eff * dev.dt)
    # ADC emits int8 spins: the chip's spin wires are 1-bit, so when the
    # spin axis is sharded the cross-shard exchange moves 4x fewer bytes
    # than f32 (§Perf ising iteration 2). Numerically exact (+-1).
    q8 = sign_pm1(v, dev.threshold, jnp.int8)                    # (P, R, N)
    q8 = _replicate_spin_axis(q8)
    sq = (q8.astype(jnp.float32) * s).astype(J.dtype)  # column scales fold
    dv = jnp.einsum("pij,prj->pri", J, sq,
                    preferred_element_type=jnp.float32)
    if noise is not None:
        dv = dv + noise
    return jnp.clip(v + dv, 0.0, dev.vdd)


def _replicate_spin_axis(q8):
    """Pin the cross-shard spin exchange to the INT8 tensor: without this
    constraint GSPMD all-gathers the post-scale f32 form (4x the bytes).
    The spin axis is forced replicated; problem/run axes stay unconstrained
    so run-sharded layouts remain communication-free."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return q8
    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    spec = jax.sharding.PartitionSpec(U, U, None)
    return jax.lax.with_sharding_constraint(q8, spec)


@functools.partial(jax.jit, static_argnames=("dev", "pert", "record_every"))
def anneal(J, v0, dev: DeviceModel, pert: PerturbationConfig,
           key: Optional[jax.Array] = None, record_every: int = 0) -> AnnealResult:
    """Run the full anneal. ``J`` must already be quantized to DAC levels
    (use ``DeviceModel.quantize``); it stays fixed — refresh/perturbation act
    through the closed-form column scales.

    key: optional PRNG key enabling the Gaussian "inherent perturbation"
        noise path (dev.noise_sigma > 0).
    record_every: if > 0, record the Hamiltonian every k steps (Fig. 4 left).
    """
    J = jnp.asarray(J, dtype=jnp.float32)
    v0 = jnp.asarray(v0, dtype=jnp.float32)
    # loop-invariant cast OUTSIDE the scan: integer DAC levels are exact in
    # bf16, halving per-step J reads (§Perf ising iteration 3)
    Jc = J.astype(jnp.dtype(dev.compute_dtype))
    n_steps = dev.n_steps
    use_noise = (key is not None) and dev.noise_sigma > 0

    def body(carry, t):
        v, k = carry
        if use_noise:
            k, sub = jax.random.split(k)
            noise = dev.noise_sigma * dev.dt * jax.random.normal(sub, v.shape, v.dtype)
        else:
            noise = None
        v = _step(v, t, Jc, dev, pert, noise)
        if record_every:
            return (v, k), ising_energy(J, dev.adc(v))
        return (v, k), None

    key = key if key is not None else jax.random.PRNGKey(0)
    (v, _), recs = jax.lax.scan(body, (v0, key), jnp.arange(n_steps, dtype=jnp.int32))
    sigma = dev.adc(v)
    energy = ising_energy(J, sigma)
    traj = None
    if record_every:
        # (T, P, R) -> (P, R, T); keep only the recorded rows.
        traj = jnp.moveaxis(recs, 0, -1)[..., ::record_every]
    return AnnealResult(v_final=v, sigma=sigma, energy=energy, energy_traj=traj)


def anneal_energy_trace(J, v0, dev, pert, record_every=4, key=None):
    """Convenience: (P, R, T) Hamiltonian trajectory for Fig. 4-style plots."""
    res = anneal(J, v0, dev, pert, key=key, record_every=record_every)
    return res.energy_traj
