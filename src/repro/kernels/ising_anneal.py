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

Two dies a tile: a die of N <= 64 spins fills at most half of a 128-lane
tile, so when a batch holds two or more such dies the wrapper packs dies
2i and 2i+1 block-diagonally into tile i (lanes [0, N) and [N, 2N)) and
the grid becomes (ceil(P/2), R/BLOCK_R): each program instance then runs
two independent chains in one step loop, at nearly one chain's step
latency. The off-diagonal blocks are zero, so every product across dies
is +-0 and each die's v_final is the one-die-a-tile kernel's bit for bit.
The packed kernels are named ``fused_anneal_kernel_x2`` and
``fused_anneal_kernel_int8_x2``.

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
LANES = 128


def dies_per_tile(P: int, N: int) -> int:
    """Dies one 128-lane kernel tile anneals for a (P, N, N) batch: two
    when dies of at most half a tile can be paired, else one."""
    return 2 if N <= LANES // 2 and P >= 2 else 1


def default_interpret() -> bool:
    """The one rule for Pallas interpret mode: compiled on TPU, interpreted
    everywhere else (interpret mode is the CPU correctness harness, never a
    fast path). Every kernel wrapper resolves ``interpret=None`` here."""
    return jax.default_backend() != "tpu"


def _anneal_kernel(j_ref, v_ref, out_ref, *, dev: DeviceModel,
                   pert: PerturbationConfig, j_dtype: str,
                   die_n: int | None):
    """One program instance: anneal BLOCK_R runs of one tile in VMEM.

    j_ref:   (1, N, N) coupling block  (VMEM; f32 / bf16 / int8 per j_dtype)
    v_ref:   (1, BLOCK_R, N) v0 block  (VMEM, f32)
    out_ref: (1, BLOCK_R, N) v_final   (VMEM, f32)
    die_n:   spins per die when the tile holds two dies (the second at
             lanes [die_n, 2*die_n)); None for one die a tile.

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
        if die_n is not None:
            # die-local column ids: the second die's columns count from 0
            # at its first lane (lanes past both dies are inert padding)
            col_ids = jnp.where(col_ids >= die_n, col_ids - die_n, col_ids)

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
    unpadded. Where :func:`dies_per_tile` is 2, pairs of dies share a tile
    (:func:`_pack_pairs`) and are unpacked here again. ``interpret=None``
    resolves through :func:`default_interpret` (compiled on TPU, interpreted
    elsewhere); ``interpret=True`` forces the CPU validation mode.
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
    dies = dies_per_tile(P, N)

    # Pad spins to the 128-lane boundary with zero couplings; padded v0 at
    # vdd (Q=+1) is inert because its rows AND columns of J are zero. The
    # in-kernel schedule assigns the phantom columns real scale values —
    # harmless for the same reason.
    r_pad = (-R) % block_r
    if dies == 2:
        J, v0 = _pack_pairs(J, v0, dev.vdd)
    else:
        n_pad = (-N) % LANES
        if n_pad:
            J = jnp.pad(J, ((0, 0), (0, n_pad), (0, n_pad)))
            v0 = jnp.pad(v0, ((0, 0), (0, 0), (0, n_pad)),
                         constant_values=dev.vdd)
    if r_pad:
        v0 = jnp.pad(v0, ((0, 0), (0, r_pad), (0, 0)),
                     constant_values=dev.vdd)
    tiles, Np = J.shape[0], J.shape[-1]
    Rp = R + r_pad
    J = J.astype(j_store)   # integer DAC levels are exact in bf16/int8

    grid = (tiles, Rp // block_r)
    kernel = functools.partial(_anneal_kernel, dev=dev, pert=pert,
                               j_dtype=j_dtype,
                               die_n=N if dies == 2 else None)
    # the kernel's name in device traces: the int8 and the packed variants
    # have their own, so a trace tells which ran
    name = "fused_anneal_kernel" + ("_int8" if j_dtype == "int8" else "") \
        + ("_x2" if dies == 2 else "")
    out = pl.pallas_call(
        kernel,
        grid=grid,
        name=name,
        in_specs=[
            pl.BlockSpec((1, Np, Np), lambda p, r: (p, 0, 0)),      # J_p
            pl.BlockSpec((1, block_r, Np), lambda p, r: (p, r, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r, Np), lambda p, r: (p, r, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles, Rp, Np), jnp.float32),
        interpret=interpret,
    )(J, v0)
    if dies == 2:
        return _unpack_pairs(out, P, R, N)
    return out[:, :R, :N]


def _pack_pairs(J, v0, vdd: float):
    """Dies 2i and 2i+1 block-diagonally in tile i: J (P,N,N) -> (ceil(P/2),
    128, 128) with die 2i at rows and columns [0, N) and die 2i+1 at
    [N, 2N), zero elsewhere; v0 (P,R,N) -> (ceil(P/2), R, 128) the same way
    along the lanes, the rest at vdd. An odd P leaves the last tile one die
    and inert padding."""
    P, N, _ = J.shape
    if P % 2:
        J = jnp.pad(J, ((0, 1), (0, 0), (0, 0)))
        v0 = jnp.pad(v0, ((0, 1), (0, 0), (0, 0)), constant_values=vdd)
    tiles, R = J.shape[0] // 2, v0.shape[1]
    Jp = (jnp.zeros((tiles, LANES, LANES), J.dtype)
          .at[:, :N, :N].set(J[0::2])
          .at[:, N:2 * N, N:2 * N].set(J[1::2]))
    rest = jnp.full((tiles, R, LANES - 2 * N), vdd, v0.dtype)
    vp = jnp.concatenate([v0[0::2], v0[1::2], rest], axis=-1)
    return Jp, vp


def _unpack_pairs(out, P: int, R: int, N: int):
    """The inverse of :func:`_pack_pairs` on the kernel's (tiles, Rp, 128)
    output: (P, R, N), die 2i from lanes [0, N) of tile i, die 2i+1 from
    [N, 2N)."""
    tiles = out.shape[0]
    v = out[:, :R, :2 * N].reshape(tiles, R, 2, N)
    return v.transpose(0, 2, 1, 3).reshape(2 * tiles, R, N)[:P]
