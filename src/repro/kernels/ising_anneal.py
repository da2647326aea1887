"""Fused VMEM anneal kernel (Pallas, TPU target) — schedule-table-free.

The paper's chip is "one-shot, fully parallel": all 64 nodes integrate all
coupling currents simultaneously, with zero data movement during the anneal
(the coupling matrix lives physically next to the nodes). The TPU analogue is
to pin the coupling block J (and the run-block voltages) in VMEM and execute
the ENTIRE anneal — T Euler steps of {ADC -> column-scale -> MXU matvec ->
integrate -> clip} — inside one kernel invocation, so HBM traffic is exactly
one read of (J, v0) and one write of v_final, independent of T.

The perturbation/leakage schedule is evaluated IN-KERNEL as the closed form
(``perturbation.scales_from_cols`` on the step index and a 2-D column iota),
not streamed as a precomputed (T, N) table. That removes the last T-dependent
VMEM tenant and the O(T*N) HBM read the chip has no analogue of: max anneal
length is now bounded only by the fori_loop trip count, and the VMEM budget
is N*N*itemsize(J) + 2*BLOCK_R*N*4 bytes (N <= ~1024 f32, ~1400 bf16).
``drive_dt`` is folded into the per-step scales outside the matvec, and J^T
is hoisted out of the step loop, so the loop body is exactly
{compare, scale, MXU dot, add, clip}.

The naive step (one matvec per HBM round-trip) has arithmetic intensity
~0.5 FLOP/byte; the fused anneal raises it by a factor of T (~10^3), moving
the solve from memory-bound to compute-bound — the same property the analog
array gets from physics.

Grid: (P problems, R/BLOCK_R run blocks). Each program instance owns one
(J_p, v-block) pair. MXU work per step: (BLOCK_R, N) @ (N, N).

j_dtype variants (mirroring the scan path's §Perf iterations 2/3):
  'float32'  — works for every schedule; full f32 on CPU, while on a TPU
               the MXU's default precision rounds the scaled spin vector
               to bf16 (bitwise equal to 'bfloat16' there).
  'bfloat16' — halves the VMEM J tenant; integer DAC levels are exact in
               bf16, the bf16 cast of the scaled spin vector rounds the
               leak-decay factor (~3 decimal digits). Exact when the
               schedule is unit (gradient-descent baseline).
  'int8'     — unit-schedule fast path: int8 spins x int8 J on the MXU with
               int32 accumulation; bit-exact vs float32 for quantized J
               (|levels| <= 15) and power-of-two drive_dt. Only valid when
               ``perturbation.unit_scales(dev, pert)`` holds — the engine
               enforces that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.binarize import sign_pm1
from ..core.device_model import DeviceModel
from ..core.perturbation import (PerturbationConfig, scales_from_cols,
                                 unit_scales)


DEFAULT_BLOCK_R = 128
J_DTYPES = ("float32", "bfloat16", "int8")


def default_interpret() -> bool:
    """The one rule for Pallas interpret mode: compiled on TPU, interpreted
    everywhere else (interpret mode is the CPU correctness harness, never a
    fast path). Every kernel wrapper resolves ``interpret=None`` here."""
    return jax.default_backend() != "tpu"


def _anneal_kernel(j_ref, v_ref, out_ref, *, dev: DeviceModel,
                   pert: PerturbationConfig, j_dtype: str):
    """One program instance: anneal BLOCK_R runs of one problem in VMEM.

    j_ref:   (1, N, N) coupling block  (VMEM; f32 / bf16 / int8 per j_dtype)
    v_ref:   (1, BLOCK_R, N) v0 block  (VMEM, f32)
    out_ref: (1, BLOCK_R, N) v_final   (VMEM, f32)

    The schedule is re-derived from the step index each iteration — O(N) VPU
    work against the O(BLOCK_R*N*N) MXU matvec, i.e. free — so no (T, N)
    operand exists and VMEM use is independent of the anneal length.
    """
    vdd = float(dev.vdd)
    thr = float(dev.threshold)
    drive_dt = float(dev.drive_eff * dev.dt)
    n = j_ref.shape[-1]
    J_t = j_ref[0].T                          # (N, N); dv = sq @ J^T

    if j_dtype == "int8":
        # Unit-schedule fast path: the column scale is identically 1, so the
        # matvec is a pure +-1 x integer-level contraction — exact in int32.
        def step(t, v):
            q8 = sign_pm1(v, thr, jnp.int8)
            acc = jnp.dot(q8, J_t, preferred_element_type=jnp.int32)
            return jnp.clip(v + acc.astype(jnp.float32) * drive_dt, 0.0, vdd)
    else:
        # TPU requires >= 2-D iota; (1, N) broadcasts over the run block.
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

        def step(t, v):
            q = sign_pm1(v, thr)
            s = scales_from_cols(t, col_ids, dev, pert) * drive_dt   # (1, N)
            sq = q * s
            if j_dtype == "bfloat16":
                sq = sq.astype(jnp.bfloat16)
            dv = jnp.dot(sq, J_t, preferred_element_type=jnp.float32)
            return jnp.clip(v + dv, 0.0, vdd)

    v = jax.lax.fori_loop(0, dev.n_steps, step, v_ref[0])
    out_ref[0] = v


@functools.partial(jax.jit,
                   static_argnames=("dev", "pert", "block_r", "j_dtype",
                                    "interpret"))
def fused_anneal_kernel(J, v0, *, dev: DeviceModel, pert: PerturbationConfig,
                        block_r: int = DEFAULT_BLOCK_R,
                        j_dtype: str = "float32",
                        interpret: bool | None = None):
    """pallas_call wrapper. J (P,N,N), v0 (P,R,N); schedule derived in-kernel
    from (dev, pert) — there is NO schedule operand.

    Pads N to a lane multiple (128) and R to block_r; returns v_final (P,R,N)
    unpadded. ``interpret=None`` resolves through :func:`default_interpret`
    (compiled on TPU, interpreted elsewhere); ``interpret=True`` forces the
    CPU validation mode.
    """
    if interpret is None:
        interpret = default_interpret()
    if j_dtype not in J_DTYPES:
        raise ValueError(f"j_dtype must be one of {J_DTYPES}, got {j_dtype!r}")
    if j_dtype == "int8" and not unit_scales(dev, pert):
        raise ValueError("int8 J path requires a unit schedule "
                         "(no perturbation, no finite leakage)")
    j_store = jnp.dtype(j_dtype)
    J = jnp.asarray(J, jnp.float32)
    v0 = jnp.asarray(v0, jnp.float32)
    P, N, _ = J.shape
    R = v0.shape[1]

    # Pad spins to the 128-lane boundary with zero couplings; padded v0 at
    # vdd (Q=+1) is inert because its rows AND columns of J are zero. The
    # in-kernel schedule assigns the phantom columns real scale values —
    # harmless for the same reason.
    n_pad = (-N) % 128
    r_pad = (-R) % block_r
    if n_pad:
        J = jnp.pad(J, ((0, 0), (0, n_pad), (0, n_pad)))
        v0 = jnp.pad(v0, ((0, 0), (0, 0), (0, n_pad)),
                     constant_values=dev.vdd)
    if r_pad:
        v0 = jnp.pad(v0, ((0, 0), (0, r_pad), (0, 0)),
                     constant_values=dev.vdd)
    Np, Rp = N + n_pad, R + r_pad
    J = J.astype(j_store)   # integer DAC levels are exact in bf16/int8

    grid = (P, Rp // block_r)
    kernel = functools.partial(_anneal_kernel, dev=dev, pert=pert,
                               j_dtype=j_dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        # the kernel's name in device traces; the int8 path has its own, so
        # a trace tells which variant ran
        name=("fused_anneal_kernel_int8" if j_dtype == "int8"
              else "fused_anneal_kernel"),
        in_specs=[
            pl.BlockSpec((1, Np, Np), lambda p, r: (p, 0, 0)),      # J_p
            pl.BlockSpec((1, block_r, Np), lambda p, r: (p, r, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r, Np), lambda p, r: (p, r, 0)),
        out_shape=jax.ShapeDtypeStruct((P, Rp, Np), jnp.float32),
        interpret=interpret,
    )(J, v0)
    return out[:, :R, :N]
