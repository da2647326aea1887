"""Fused VMEM simulated-bifurcation kernel (Pallas) — aSB / bSB / dSB.

Simulated bifurcation (Goto et al.) evolves a classical Hamiltonian system
of positions x and momenta y per spin under a pump ``a(t)`` ramped from 0
to ``a0``: below the bifurcation each x sits at 0, and as the pump crosses
the threshold every oscillator falls into one of two wells whose signs
encode a low-energy Ising state. The inner loop is a dense ``J @ x`` —
the same MXU-shaped work as the fused anneal kernel — so the port reuses
that kernel's architecture wholesale:

  * grid ``(P problems, R/BLOCK_R restart blocks)``; J pinned in VMEM per
    problem, the whole integration under one ``fori_loop``;
  * the pump schedule is derived IN-KERNEL from the step index
    (``a_t = a0 * (t+1) / n_steps``) — no (T,) operand, VMEM independent
    of the epoch count, exactly like the anneal kernel's closed-form
    column scales;
  * HBM traffic is one read of (Jc, x0, y0) and one write of x_final,
    independent of T. VMEM budget: ``N^2*4 + 3*BLOCK_R*N*4`` bytes.

Variants (one symplectic-Euler step, position first — the ordering of the
aSB exemplar in SNIPPETS.md Snippet 2):

  aSB  x += a0*y*dt;  y += (-(x^2 + a0 - a_t)*x + Jc @ x)*dt
  bSB  drops the Kerr x^3 term and adds perfectly inelastic walls:
       |x| > 1 -> x = sign(x), y = 0
  dSB  like bSB but the coupling drive is the BINARIZED position
       Jc @ sign_pm1(x) — the discrete feedback that makes dSB the
       strongest variant on dense Max-Cut.

The coupling strength c0 is folded into Jc by the caller (it is
per-problem; see ``solvers.sb_jax``), so the kernel takes no per-problem
scalar operand. Padded spins ride for free: zero Jc rows/columns and
x0 = y0 = 0 keep them at exactly 0 for the whole trajectory (every update
term is a product with 0, and IEEE adds of 0 are exact), and the
``sign_pm1`` readout then maps them to +1 — the same pinned-pad convention
as tabu-jax.

``interpret=True`` (what the default resolves to off-TPU) traces the identical jnp ops into
XLA, which is why ``sb_reference`` below — the same step expressions under
a host-side ``lax.scan`` — matches the kernel bit-for-bit and serves as
the parity oracle in tests/test_sb_jax.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.binarize import sign_pm1
from .ising_anneal import default_interpret

DEFAULT_BLOCK_R = 128
SB_VARIANTS = ("aSB", "bSB", "dSB")


def _sb_step(x, y, J_t, a_t, *, variant: str, dt: float, a0: float):
    """One symplectic SB step on an (r, N) position/momentum block.

    Shared verbatim by the Pallas kernel body and the ``sb_reference``
    scan oracle so the two paths are the same op sequence (bitwise parity
    is a test contract, like the anneal kernel vs fused_anneal_ref).
    """
    x = x + (a0 * dt) * y
    drive = sign_pm1(x) if variant == "dSB" else x
    dv = jnp.dot(drive, J_t, preferred_element_type=jnp.float32)
    if variant == "aSB":
        y = y + dt * (dv - (x * x + (a0 - a_t)) * x)
    else:
        y = y + dt * (dv - (a0 - a_t) * x)
        # Perfectly inelastic walls: positions saturate at the well edge
        # and the momentum is absorbed (Goto's bSB stabilization).
        hit = jnp.abs(x) > 1.0
        x = jnp.clip(x, -1.0, 1.0)
        y = jnp.where(hit, 0.0, y)
    return x, y


def _sb_kernel(j_ref, x_ref, y_ref, out_ref, *, variant: str, n_steps: int,
               dt: float, a0: float):
    """One program instance: integrate BLOCK_R restarts of one problem.

    j_ref:   (1, N, N) c0-scaled couplings (VMEM, f32)
    x_ref:   (1, BLOCK_R, N) x0 block      (VMEM, f32)
    y_ref:   (1, BLOCK_R, N) y0 block      (VMEM, f32)
    out_ref: (1, BLOCK_R, N) x_final      (VMEM, f32)
    """
    J_t = j_ref[0].T                         # (N, N); dv = drive @ Jc^T
    inv_steps = 1.0 / float(n_steps)

    def step(t, xy):
        x, y = xy
        # Linear pump ramp 0 -> a0, derived from the step index (no
        # (T,) operand): a_t after step t+1 of n_steps.
        a_t = a0 * ((t + 1).astype(jnp.float32) * inv_steps)
        return _sb_step(x, y, J_t, a_t, variant=variant, dt=dt, a0=a0)

    x, _ = jax.lax.fori_loop(0, n_steps, step, (x_ref[0], y_ref[0]))
    out_ref[0] = x


@functools.partial(jax.jit,
                   static_argnames=("variant", "n_steps", "dt", "a0",
                                    "block_r", "interpret"))
def fused_sb_kernel(Jc, x0, y0, *, variant: str = "bSB", n_steps: int = 400,
                    dt: float = 0.5, a0: float = 1.0,
                    block_r: int = DEFAULT_BLOCK_R,
                    interpret: bool | None = None):
    """pallas_call wrapper. Jc (P,N,N) c0-scaled couplings, x0/y0 (P,R,N).

    Returns x_final (P, R, N) float32 (continuous positions — callers
    binarize with ``sign_pm1``). Pads N to the 128-lane boundary and R to
    block_r with zeros; zero-state + zero-coupling pads are exactly inert,
    so the trim is exact. ``interpret=None`` resolves through
    ``default_interpret`` (compiled on TPU, traced jnp ops elsewhere).
    """
    if interpret is None:
        interpret = default_interpret()
    if variant not in SB_VARIANTS:
        raise ValueError(f"variant must be one of {SB_VARIANTS}, "
                         f"got {variant!r}")
    Jc = jnp.asarray(Jc, jnp.float32)
    x0 = jnp.asarray(x0, jnp.float32)
    y0 = jnp.asarray(y0, jnp.float32)
    P, N, _ = Jc.shape
    R = x0.shape[1]

    n_pad = (-N) % 128
    r_pad = (-R) % block_r
    if n_pad:
        Jc = jnp.pad(Jc, ((0, 0), (0, n_pad), (0, n_pad)))
        x0 = jnp.pad(x0, ((0, 0), (0, 0), (0, n_pad)))
        y0 = jnp.pad(y0, ((0, 0), (0, 0), (0, n_pad)))
    if r_pad:
        x0 = jnp.pad(x0, ((0, 0), (0, r_pad), (0, 0)))
        y0 = jnp.pad(y0, ((0, 0), (0, r_pad), (0, 0)))
    Np, Rp = N + n_pad, R + r_pad

    grid = (P, Rp // block_r)
    kernel = functools.partial(_sb_kernel, variant=variant,
                               n_steps=int(n_steps), dt=float(dt),
                               a0=float(a0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        name="sb_anneal_kernel",   # the kernel's name in device traces
        in_specs=[
            pl.BlockSpec((1, Np, Np), lambda p, r: (p, 0, 0)),      # Jc_p
            pl.BlockSpec((1, block_r, Np), lambda p, r: (p, r, 0)),  # x0
            pl.BlockSpec((1, block_r, Np), lambda p, r: (p, r, 0)),  # y0
        ],
        out_specs=pl.BlockSpec((1, block_r, Np), lambda p, r: (p, r, 0)),
        out_shape=jax.ShapeDtypeStruct((P, Rp, Np), jnp.float32),
        interpret=interpret,
    )(Jc, x0, y0)
    return out[:, :R, :N]


@functools.partial(jax.jit, static_argnames=("variant", "n_steps", "dt",
                                             "a0"))
def sb_reference(Jc, x0, y0, *, variant: str = "bSB", n_steps: int = 400,
                 dt: float = 0.5, a0: float = 1.0):
    """Pure-``lax.scan`` oracle for the fused kernel (parity contract).

    Runs the SAME ``_sb_step`` expressions per (problem, full restart
    block), with the SAME 128-lane N padding the kernel applies so the
    matvec contraction dimension matches — tests assert the kernel output
    is bit-identical (pass ``block_r=R`` to the kernel so the gemm shapes
    agree too).
    """
    if variant not in SB_VARIANTS:
        raise ValueError(f"variant must be one of {SB_VARIANTS}, "
                         f"got {variant!r}")
    Jc = jnp.asarray(Jc, jnp.float32)
    x0 = jnp.asarray(x0, jnp.float32)
    y0 = jnp.asarray(y0, jnp.float32)
    N = Jc.shape[-1]
    n_pad = (-N) % 128
    if n_pad:
        Jc = jnp.pad(Jc, ((0, 0), (0, n_pad), (0, n_pad)))
        x0 = jnp.pad(x0, ((0, 0), (0, 0), (0, n_pad)))
        y0 = jnp.pad(y0, ((0, 0), (0, 0), (0, n_pad)))
    inv_steps = 1.0 / float(n_steps)

    def per_problem(Jp, xp, yp):
        J_t = Jp.T

        def step(xy, t):
            x, y = xy
            a_t = a0 * ((t + 1).astype(jnp.float32) * inv_steps)
            return (_sb_step(x, y, J_t, a_t, variant=variant, dt=dt,
                             a0=a0), None)

        (x, _), _ = jax.lax.scan(step, (xp, yp), jnp.arange(n_steps))
        return x

    return jax.vmap(per_problem)(Jc, x0, y0)[:, :, :N]
