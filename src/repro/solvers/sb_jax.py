"""Simulated bifurcation (aSB / bSB / dSB) at machine batch scale.

The state-of-the-art classical competitor on dense Max-Cut, ported to the
same one-dispatch-per-bucket shape as tabu-jax / pt-jax: (problems ×
restarts) integrated by the fused Pallas kernel in
``kernels.sb_kernel`` (J pinned in VMEM, the pump ramp derived in-kernel
from the step index). This module owns everything per-problem:

  * the coupling normalization ``c0 = 0.5 / (sigma_J * sqrt(n))`` with
    ``sigma_J = sqrt(sum(J^2) / (n^2 - n))`` — the exemplar's scaling
    (SNIPPETS.md Snippet 2), computed from each problem's TRUE size so a
    padded bucket normalizes exactly like the unpadded problem would
    (the zero pad rows add nothing to ``sum(J^2)``);
  * restart initialization: x0, y0 ~ U(-0.1, 0.1) per (problem, restart),
    masked to zero on padded spins (a zero-state, zero-coupling pad is
    exactly inert through the dynamics and reads +1 at the sign_pm1
    readout — the tabu-jax pinned-pad convention);
  * sign-binarized readout through the ONE ``core.binarize.sign_pm1``
    convention (``jnp.sign(0)`` would emit 0-spins), and float64 energy
    scoring on the host against the ORIGINAL unscaled J.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.binarize import sign_pm1
from ..kernels.sb_kernel import SB_VARIANTS, fused_sb_kernel

#: init amplitude for positions/momenta (standard SB practice: start just
#: off the unstable x=0 fixed point so restarts decorrelate).
INIT_AMP = 0.1


def sb_coupling_scale(J, n_true=None):
    """Per-problem c0 for (P, n, n) level-space couplings (numpy, float64).

    ``c0 = 0.5 / (sigma_J * sqrt(n_true))`` with ``sigma_J`` the RMS
    off-diagonal coupling over the TRUE n_true*(n_true-1) directed pairs —
    zero pad rows/columns don't perturb it. Degenerate problems (n <= 1 or
    all-zero J) get c0 = 1.0 so the dynamics stay finite.
    """
    J = np.asarray(J, np.float64)
    if J.ndim == 2:
        J = J[None]
    P, n = J.shape[0], J.shape[-1]
    nt = (np.full((P,), n, np.int64) if n_true is None
          else np.asarray(n_true, np.int64))
    ss = (J * J).sum(axis=(1, 2))
    pairs = np.maximum(nt * (nt - 1), 1)
    sigma = np.sqrt(ss / pairs)
    good = sigma > 0
    c0 = np.ones((P,), np.float64)
    c0[good] = 0.5 / (sigma[good] * np.sqrt(nt[good].astype(np.float64)))
    return c0


def sb_inits(P, n_restarts, n, n_true=None, seed: int = 0):
    """x0, y0 ~ U(-INIT_AMP, INIT_AMP), (P, R, n) f32, padded spins zeroed.

    Streams fold in the problem index, so a problem's draws depend only on
    (seed, p) — prefix-stable as the restart batch grows along R's last
    axis is NOT guaranteed, but same (seed, P, R, n) is bit-reproducible.
    """
    base = jax.random.PRNGKey(seed)
    keys = jax.random.split(base, P)
    u = jax.vmap(lambda k: jax.random.uniform(
        k, (2, n_restarts, n), jnp.float32,
        minval=-INIT_AMP, maxval=INIT_AMP))(keys)        # (P, 2, R, n)
    if n_true is not None:
        valid = (jnp.arange(n)[None, None, None, :]
                 < jnp.asarray(n_true, jnp.int32)[:, None, None, None])
        u = jnp.where(valid, u, 0.0)
    return u[:, 0], u[:, 1]


def simulated_bifurcation_jax_runs(J, n_true=None, variant: str = "bSB",
                                   n_steps: int = 400, n_restarts: int = 16,
                                   dt: float = 0.5, a0: float = 1.0,
                                   seed: int = 0, block_r=None,
                                   interpret=None):
    """Per-restart SB results for a (padded) problem batch, one dispatch.

    J: (P, n, n) or (n, n) level-space couplings (rows/cols >= each
    problem's true size must be zero — suite-bucket padding). ``n_true``:
    (P,) true spin counts (default: full n). Returns ``(energies (P, R)
    float64, sigma (P, R, n) int8)`` — energies scored on the host in
    float64 against the ORIGINAL J; padded spins read +1. ``interpret``
    defaults to the kernel's backend rule (compiled on TPU).
    """
    if variant not in SB_VARIANTS:
        raise ValueError(f"variant must be one of {SB_VARIANTS}, "
                         f"got {variant!r}")
    J = np.asarray(J, np.float32)
    if J.ndim == 2:
        J = J[None]
    P, n = J.shape[0], J.shape[-1]
    R = int(n_restarts)

    c0 = sb_coupling_scale(J, n_true)
    Jc = jnp.asarray((J.astype(np.float64)
                      * c0[:, None, None]).astype(np.float32))
    x0, y0 = sb_inits(P, R, n, n_true=n_true, seed=seed)
    if block_r is None:
        block_r = min(max(8, R), 128)
    x = fused_sb_kernel(Jc, x0, y0, variant=variant, n_steps=int(n_steps),
                        dt=float(dt), a0=float(a0), block_r=int(block_r),
                        interpret=interpret)
    sig = np.asarray(sign_pm1(x, dtype=jnp.int8))         # (P, R, n)

    s64 = sig.astype(np.float64)
    J64 = J.astype(np.float64)
    e = -0.5 * np.einsum("pri,pij,prj->pr", s64, J64, s64)
    return e, sig


def simulated_bifurcation_jax(J, variant: str = "bSB", n_steps: int = 400,
                              n_restarts: int = 16, dt: float = 0.5,
                              a0: float = 1.0, seed: int = 0):
    """Best-of-restarts view. J (n, n) or (P, n, n); returns
    (best_energy, best_sigma) — scalars / (n,) int8 for a single problem,
    (P,) / (P, n) for a batch."""
    single = np.ndim(J) == 2
    e, s = simulated_bifurcation_jax_runs(
        J, variant=variant, n_steps=n_steps, n_restarts=n_restarts,
        dt=dt, a0=a0, seed=seed)
    best = np.argmin(e, axis=1)
    best_e = e[np.arange(e.shape[0]), best]
    best_s = s[np.arange(e.shape[0]), best]
    if single:
        return float(best_e[0]), best_s[0]
    return best_e, best_s
