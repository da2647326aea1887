"""Plain reference of the die's anneal (paper Eq. 3-6 with section III's
landscape perturbation), in straightforward JAX.

    dv_i = dd * sum_j J_ij * round_p(s_j(t) * Q(v_j)),   v clipped to [0, vdd]

Q is the inverter ADC (v >= vdd/2 -> +1, else -1), s(t) the closed-form
column schedule (round-robin refresh of 64 columns, 8 Euler sub-steps per
column slot, rails gated off for 8 of every 48 slots until the last settle
sweep, gate leakage exp(-age / (64 * tau))), and dd = drive * dt = 1/512.

``precision`` is the rounding ``round_p`` of the scaled spin operand:
``bfloat16`` is what the configuration states (the TPU MXU's one-pass
product of float32 operands at default precision, float32 accumulation);
``float8_e4m3fn`` is the control, one step below. J holds integer levels
(and, in the fabric, integer boundary fields), so every product and every
partial sum of the bf16 contraction is exact in float32 and the result
cannot depend on summation order: a sound implementation agrees bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

VDD = 1.0
THRESHOLD = 0.5
COLS = 64              # refresh pointer width of one die
SUBSTEPS = 8           # Euler sub-steps per column slot
ANNEAL_SWEEPS = 3.75   # 3 us / 0.8 us per sweep
TAU_LEAK_SWEEPS = 10.0
PERIOD_SLOTS = 48
OFF_SLOTS = 8
SETTLE_SWEEPS = 1.0
N_STEPS = int(round(ANNEAL_SWEEPS * COLS * SUBSTEPS))     # 1920
DRIVE_DT = 1.0 / (COLS * SUBSTEPS)                        # drive 1.0 V

PRECISIONS = ("bfloat16", "float8_e4m3fn")


def schedule(n_cols: int, n_steps: int = N_STEPS) -> jax.Array:
    """(n_steps, n_cols) float32 column scales s_j(t), on the default
    device, in the float32 arithmetic of the stated schedule."""
    t = jnp.arange(n_steps, dtype=jnp.int32)[:, None]
    j = jnp.arange(n_cols, dtype=jnp.int32)[None, :] % COLS
    slot = t // SUBSTEPS
    last = slot - jnp.mod(slot - j, COLS)
    pre = last < 0
    last = jnp.where(pre, j - COLS, last)
    settle_start = (ANNEAL_SWEEPS - SETTLE_SWEEPS) * COLS
    off = (jnp.mod(last, PERIOD_SLOTS) < OFF_SLOTS) & ~pre \
        & (last < settle_start)
    age = t.astype(jnp.float32) / SUBSTEPS - last.astype(jnp.float32)
    decay = jnp.exp(-age / (COLS * TAU_LEAK_SWEEPS))
    return jnp.where(off, jnp.float32(0.0), decay).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("precision",))
def anneal(J, v0, table, precision: str = "bfloat16"):
    """Anneal J (B, N, N) float32 from v0 (B, R, N) float32 under the
    column schedule ``table`` (T, N). Returns (v_final, sigma int8)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    op = jnp.dtype(precision)
    Jc = J.astype(jnp.bfloat16)

    def step(t, v):
        q = jnp.where(v >= THRESHOLD, 1.0, -1.0).astype(jnp.float32)
        sq = (q * table[t][None, None, :]).astype(op).astype(jnp.bfloat16)
        acc = jnp.einsum("brj,bij->bri", sq, Jc,
                         preferred_element_type=jnp.float32)
        return jnp.clip(v + acc * jnp.float32(DRIVE_DT), 0.0, VDD)

    v = jax.lax.fori_loop(0, table.shape[0], step, v0)
    return v, jnp.where(v >= THRESHOLD, 1, -1).astype(jnp.int8)


def run(J, v0, precision: str = "bfloat16", block: int = 64):
    """Anneal in blocks of ``block`` problems (so a large sample fits) and
    return host arrays (sigma (B, R, N) int8, energy (B, R) float64)."""
    J = np.asarray(J, dtype=np.float32)
    v0 = np.asarray(v0, dtype=np.float32)
    block = min(block, J.shape[0])
    table = schedule(J.shape[-1])
    sig = []
    for i in range(0, J.shape[0], block):
        Jb, vb = J[i:i + block], v0[i:i + block]
        pad = block - Jb.shape[0]
        if pad:   # one compiled shape for every block
            Jb = np.concatenate([Jb, np.zeros((pad,) + Jb.shape[1:],
                                              np.float32)])
            vb = np.concatenate([vb, np.full((pad,) + vb.shape[1:], VDD,
                                             np.float32)])
        _, s = anneal(jnp.asarray(Jb), jnp.asarray(vb), table,
                      precision=precision)
        sig.append(np.asarray(s)[:block - pad])
    sig = np.concatenate(sig)
    s64 = sig.astype(np.float64)
    e = -0.5 * np.einsum("bri,bij,brj->br", s64, J.astype(np.float64), s64)
    return sig, e
