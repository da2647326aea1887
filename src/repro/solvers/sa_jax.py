"""On-device simulated annealing — the numpy SA baseline ported to JAX.

``solvers.sa.simulated_annealing`` is a host-side numpy loop: fine for a
handful of restarts, but it cannot ride the same batch scale as the Ising
machine (thousands of runs x problems on an accelerator). This port keeps
the algorithm IDENTICAL — Metropolis single-flip, geometric beta schedule,
random spin order per sweep, O(N) incremental local-field updates — and
restructures it for the device:

  * restarts are vmapped (one (n,)-state SA per restart key),
  * problems are vmapped over the restart batch,
  * sweeps run under lax.scan with the spin loop as a fori_loop,

so SR/TTS baselines run on-device at the same (P, R) scale as the machine
itself. RNG streams differ from numpy's Generator, so trajectories are not
bitwise comparable — but on problems both solvers converge on, the best
energies agree exactly (asserted by tests/test_engine.py and recorded in
BENCH_kernel.json).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.hamiltonian import EXACT


def random_init_state(J, key):
    """Uniform ±1 spins plus consistent local fields / energy. J (n,n)."""
    n = J.shape[-1]
    s = jnp.where(jax.random.bernoulli(key, 0.5, (n,)), 1.0, -1.0)
    f = J @ s                                    # (n,) local fields
    e = -0.5 * jnp.dot(s, f, precision=EXACT)    # |f| can exceed 256
    return s, f, e


def metropolis_sweep(J, s, f, e, beta, key):
    """One Metropolis sweep (random spin order, O(N) incremental field
    updates) at inverse temperature ``beta``. The shared single-rung kernel:
    SA scans it over a beta schedule, parallel tempering (``solvers.pt_jax``)
    vmaps it over a fixed temperature ladder. Returns updated (s, f, e)."""
    n = J.shape[-1]
    k_ord, k_u = jax.random.split(key)
    order = jax.random.permutation(k_ord, n)
    u = jax.random.uniform(k_u, (n,))

    def flip(i, st):
        s, f, e = st
        k = order[i]
        dH = 2.0 * s[k] * f[k]
        accept = (dH <= 0.0) | (u[i] < jnp.exp(-beta *
                                               jnp.maximum(dH, 0.0)))
        upd = jnp.where(accept, -2.0 * s[k], 0.0)        # change in s_k
        f = f + upd * J[:, k]
        s = s.at[k].set(jnp.where(accept, -s[k], s[k]))
        e = e + jnp.where(accept, dH, 0.0)
        return (s, f, e)

    return jax.lax.fori_loop(0, n, flip, (s, f, e))


def _sa_single(J, key, betas):
    """One restart: anneal a single spin vector. J (n,n), betas (T,)."""
    k_init, k_run = jax.random.split(key)
    s, f, e = random_init_state(J, k_init)

    def sweep(carry, inp):
        s, f, e, best_e, best_s = carry
        beta, kk = inp
        s, f, e = metropolis_sweep(J, s, f, e, beta, kk)
        better = e < best_e
        best_e = jnp.where(better, e, best_e)
        best_s = jnp.where(better, s, best_s)
        return (s, f, e, best_e, best_s), None

    keys = jax.random.split(k_run, betas.shape[0])
    (_, _, _, best_e, best_s), _ = jax.lax.scan(
        sweep, (s, f, e, e, s), (betas, keys))
    return best_e, best_s


@functools.partial(jax.jit, static_argnames=("n_sweeps", "n_restarts"))
def _sa_problem(J, key, n_sweeps: int, n_restarts: int,
                beta0: float, beta1: float):
    """All restarts of one problem. Returns (best_e scalar, best_s (n,))."""
    best_e, best_s = _sa_problem_all(J, key, n_sweeps, n_restarts,
                                     beta0, beta1)
    i = jnp.argmin(best_e)
    return best_e[i], best_s[i]


@functools.partial(jax.jit, static_argnames=("n_sweeps", "n_restarts"))
def _sa_problem_all(J, key, n_sweeps: int, n_restarts: int,
                    beta0: float, beta1: float):
    """All restarts of one problem, per-restart results: ((R,), (R, n))."""
    betas = beta0 * (beta1 / beta0) ** (jnp.arange(n_sweeps, dtype=jnp.float32)
                                        / max(n_sweeps - 1, 1))
    keys = jax.random.split(key, n_restarts)
    return jax.vmap(lambda k: _sa_single(J, k, betas))(keys)


def simulated_annealing_jax_runs(J, n_runs: int = 16, n_sweeps: int = 200,
                                 beta0: float = 0.05, beta1: float = 4.0,
                                 seed: int = 0):
    """Per-run SA energies for the SolveReport schema.

    J: (P, n, n). Returns (energies (P, R) float64, sigma (P, R, n) int8) —
    each restart reported as an independent run, same batching as the Ising
    machine itself (problems and restarts vmapped on device).
    """
    J = jnp.asarray(J, jnp.float32)
    if J.ndim == 2:
        J = J[None]
    P = J.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), P)
    e, s = jax.vmap(
        lambda Jp, kp: _sa_problem_all(Jp, kp, n_sweeps, n_runs,
                                       beta0, beta1))(J, keys)
    return (np.asarray(e, dtype=np.float64),
            np.asarray(s).astype(np.int8))


def simulated_annealing_jax(J, n_sweeps: int = 200, n_restarts: int = 16,
                            beta0: float = 0.05, beta1: float = 4.0,
                            seed: int = 0):
    """Drop-in JAX counterpart of ``simulated_annealing``.

    J: (n, n) or (P, n, n). Returns (best_energy, best_sigma) — scalars /
    (n,) for a single problem, (P,) / (P, n) for a batch. sigma is int8.
    """
    J = jnp.asarray(J, jnp.float32)
    single = J.ndim == 2
    if single:
        J = J[None]
    P = J.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), P)
    best_e, best_s = jax.vmap(
        lambda Jp, kp: _sa_problem(Jp, kp, n_sweeps, n_restarts,
                                   beta0, beta1))(J, keys)
    best_e = np.asarray(best_e, dtype=np.float64)
    best_s = np.asarray(best_s).astype(np.int8)
    if single:
        return float(best_e[0]), best_s[0]
    return best_e, best_s
