"""Plain reference of the die's anneal without landscape perturbation: the
paper's comparison arm (section IV), in straightforward JAX.

    dv_i = dd * sum_j J_ij * Q(v_j),   v clipped to [0, vdd]

The column schedule is identically one: rails always on, ideal refresh (no
leakage), no noise. Q is the inverter ADC (v >= vdd/2 -> +1, else -1) and
dd = drive * dt = 1/512, as in ``reference.anneal``.

The contraction is float32 at ``precision=HIGHEST``, the voltages float32.
That is exact for this arithmetic: every operand is +-1 or an integer
level |J_ij| <= 15, so every product is an integer and every partial sum
an integer of magnitude at most 64 * 15 = 960, far below 2^24; float32
holds all of them exactly whatever the order of summation, as do int8
operands with int32 accumulation. dd is a power of two, so ``sum * dd`` is
exact too, and the one rounded quantity is the float32 voltage update
``v + sum * dd``. A sound program that states int8 (or bfloat16, or
float32) operands under a unit schedule therefore agrees with this
reference bit for bit.

``state`` is the width the voltages are held at between steps: ``float32``
is what the configuration states; ``bfloat16`` rounds them on every step,
which the control (``bench/control_unit.py``) uses as the one quantity held
below the stated precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.anneal import DRIVE_DT, N_STEPS, THRESHOLD, VDD

#: the precisions a configuration may state for which this reference is
#: exact (see above)
EXACT_FOR = ("int8", "bfloat16", "float32")
STATES = ("float32", "bfloat16")


@functools.partial(jax.jit, static_argnames=("n_steps", "state"))
def anneal(J, v0, n_steps: int = N_STEPS, state: str = "float32"):
    """Anneal J (B, N, N) float32 from v0 (B, R, N) float32 under the unit
    schedule. Returns (v_final, sigma int8)."""
    if state not in STATES:
        raise ValueError(f"state must be one of {STATES}")
    held = jnp.finfo(jnp.dtype(state))

    def step(_, v):
        q = jnp.where(v >= THRESHOLD, 1.0, -1.0).astype(jnp.float32)
        acc = jnp.einsum("brj,bij->bri", q, J,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        v = jnp.clip(v + acc * jnp.float32(DRIVE_DT), 0.0, VDD)
        if state == "float32":
            return v
        # an explicit rounding: the TPU compiler, allowed excess precision,
        # drops a float32 -> bfloat16 -> float32 round trip
        return jax.lax.reduce_precision(v, held.nexp, held.nmant)

    v = jax.lax.fori_loop(0, n_steps, step, v0.astype(jnp.float32))
    return v, jnp.where(v >= THRESHOLD, 1, -1).astype(jnp.int8)


def run(J, v0, n_steps: int = N_STEPS, state: str = "float32",
        block: int = 16):
    """Anneal in blocks of ``block`` problems (so a sample of 16 problems x
    1024 runs is one block) and return host arrays (sigma (B, R, N) int8,
    energy (B, R) float64)."""
    J = np.asarray(J, dtype=np.float32)
    v0 = np.asarray(v0, dtype=np.float32)
    block = min(block, J.shape[0])
    sig = []
    for i in range(0, J.shape[0], block):
        Jb, vb = J[i:i + block], v0[i:i + block]
        pad = block - Jb.shape[0]
        if pad:   # one compiled shape for every block
            Jb = np.concatenate([Jb, np.zeros((pad,) + Jb.shape[1:],
                                              np.float32)])
            vb = np.concatenate([vb, np.full((pad,) + vb.shape[1:], VDD,
                                             np.float32)])
        _, s = anneal(jnp.asarray(Jb), jnp.asarray(vb), n_steps=n_steps,
                      state=state)
        sig.append(np.asarray(s)[:block - pad])
    sig = np.concatenate(sig)
    s64 = sig.astype(np.float64)
    e = -0.5 * np.einsum("bri,bij,brj->br", s64, J.astype(np.float64), s64)
    return sig, e
