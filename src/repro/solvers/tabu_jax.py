"""On-device tabu search — the best-known oracle ported to JAX.

``solvers.tabu.tabu_search`` is the paper's qbsolv-style oracle, but as a
host-side numpy double loop (restarts × iterations) it is the slowest,
least-batched solver in the tree: one dispatch per problem, ~100 anneals/s.
This port keeps the algorithm IDENTICAL — best-improvement single flip,
tabu tenure with aspiration, O(N) incremental local-field updates, and the
same stop-early semantics when every move is tabu and none aspirates — and
restructures it for the device:

  * restarts are vmapped (one (n,)-state search per restart key),
  * problems are vmapped over the restart batch (one (P, R) dispatch),
  * iterations run under ``lax.scan`` in lockstep across the whole batch,
    with tenure masking, aspiration, the stall ``break``, and per-problem
    iteration budgets all branch-free (``where``-masked, latched ``done``).

Padded problems are first-class: a suite bucket pads every instance up to
the chip block with zero couplings, and a padded spin's flip is a zero-dH
move that best-improvement tabu WOULD take in preference to a worsening
escape move (unlike Metropolis SA, where it is a harmless no-op). The
``n_true`` argument masks those columns out of the candidate set entirely,
so the padded search visits exactly the moves the unpadded one does.

RNG streams differ from numpy's Generator, so trajectories are not bitwise
comparable — but on problems both solvers converge on, best energies agree
exactly (asserted by tests/test_search_jax.py, like ``sa_jax``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.hamiltonian import EXACT

#: aspiration / improvement tolerance. Level-space energies are exact
#: integers (integer J, ±1 spins), comfortably inside float32's 2^24
#: integer range — anything below 0.5 distinguishes them.
_EPS = 1e-4


def _tabu_single(J, key, n_true, n_iters, tenure, max_iters: int,
                 patience, kick_len):
    """One restart on one (padded) problem. J (n, n); n_true / n_iters /
    tenure / patience / kick_len are per-problem scalars (traced);
    max_iters is the static scan length (>= n_iters). Returns
    (best_e, best_s, iters_used)."""
    n = J.shape[-1]
    valid = jnp.arange(n) < n_true               # mask padded spins
    k_init, k_kick = jax.random.split(key)
    s = jnp.where(jax.random.bernoulli(k_init, 0.5, (n,)), 1.0, -1.0)
    s = jnp.where(valid, s, 1.0)                 # padded spins pinned (inert)
    f = J @ s
    e = -0.5 * jnp.dot(s, f, precision=EXACT)    # |f| can exceed 256

    def step(carry, it):
        s, f, e, best_e, best_s, tabu_until, done, used, since = carry
        dH = 2.0 * s * f                         # (n,)
        cand = e + dH
        allowed = valid & ((tabu_until < it) | (cand < best_e - _EPS))
        masked = jnp.where(allowed, cand, jnp.inf)
        k_best = jnp.argmin(masked)
        stall = ~jnp.isfinite(masked[k_best])    # all tabu, none aspirates
        # Kick burst: after ``patience`` non-improving moves, take
        # ``kick_len`` random (non-best) flips — an O(N) iterated-local-
        # search perturbation a lockstep restart gets for free, where the
        # numpy loop would sit in a tabu cycle to the end of its budget.
        kicking = (patience > 0) & (since >= patience)
        k_rand = jax.random.randint(jax.random.fold_in(k_kick, it),
                                    (), 0, n_true)
        k = jnp.where(kicking, k_rand, k_best)
        budget_left = (~done) & (it < n_iters)
        active = budget_left & (kicking | ~stall)

        e = jnp.where(active, cand[k], e)
        f = f - jnp.where(active, 2.0 * s[k], 0.0) * J[:, k]
        s = s.at[k].set(jnp.where(active, -s[k], s[k]))
        tabu_until = tabu_until.at[k].set(
            jnp.where(active, it + tenure, tabu_until[k]))
        improved = active & (e < best_e - _EPS)
        best_e = jnp.where(improved, e, best_e)
        best_s = jnp.where(improved, s, best_s)
        done = done | (stall & (patience <= 0))  # numpy's break, latched
        used = used + active.astype(jnp.int32)
        # ``since`` counts non-improving ATTEMPTS (a stalled-but-not-yet-
        # kicking iteration still advances it toward the kick threshold)
        since = jnp.where(improved | (since >= patience + kick_len - 1),
                          0, since + budget_left.astype(jnp.int32))
        return (s, f, e, best_e, best_s, tabu_until, done, used, since), None

    tabu_until = jnp.full((n,), -1, dtype=jnp.int32)
    carry = (s, f, e, e, s, tabu_until, jnp.bool_(False), jnp.int32(0),
             jnp.int32(0))
    carry, _ = jax.lax.scan(step, carry, jnp.arange(max_iters))
    _, _, _, best_e, best_s, _, _, used, _ = carry
    return best_e, best_s, used


@functools.partial(jax.jit, static_argnames=("n_restarts", "max_iters"))
def _tabu_batch(J, keys, n_true, n_iters, tenure, patience, kick_len,
                n_restarts: int, max_iters: int):
    """(P, n, n) problems × R restarts in one dispatch."""
    def per_problem(Jp, kp, nt, ni, tn, pt, kl):
        ks = jax.random.split(kp, n_restarts)
        return jax.vmap(lambda k: _tabu_single(Jp, k, nt, ni, tn,
                                               max_iters, pt, kl))(ks)
    return jax.vmap(per_problem)(J, keys, n_true, n_iters, tenure,
                                 patience, kick_len)


def tabu_search_jax_runs(J, n_true=None, n_iters=None, n_restarts: int = 8,
                         tenure=None, seed: int = 0, patience=None,
                         kick_len=None):
    """Per-restart tabu results for a (padded) problem batch, one dispatch.

    J: (P, n, n) or (n, n) level-space couplings (rows/cols >= each
    problem's true size must be zero — suite-bucket padding). ``n_true``:
    (P,) true spin counts (default: full n). Per-problem defaults match the
    numpy oracle: ``n_iters = 40 * n_true``, ``tenure = max(4, n_true //
    4)``. The scan runs ``max(n_iters)`` lockstep iterations; problems with
    smaller budgets simply stop flipping (masked), so per-problem budgets
    are honored exactly.

    ``patience`` / ``kick_len`` add an iterated-local-search perturbation
    the lockstep batch gets for free: after ``patience`` consecutive
    non-improving iterations a restart takes ``kick_len`` random flips and
    resumes tabu descent (default: ``patience = 8 * tenure``, ``kick_len =
    tenure``). ``patience=0`` disables kicks — then the search replicates
    the numpy oracle's semantics exactly, including its stall ``break``.

    Returns ``(energies (P, R) float64, sigma (P, R, n) int8, iters_used
    (P, R) int64)`` — iters_used counts APPLIED flips, which can fall short
    of the budget when a restart stalls (every move tabu, none aspirating;
    the numpy implementation ``break``s at the same point).
    """
    J = jnp.asarray(J, jnp.float32)
    if J.ndim == 2:
        J = J[None]
    P, n = J.shape[0], J.shape[-1]
    n_true = (jnp.full((P,), n, jnp.int32) if n_true is None
              else jnp.asarray(n_true, jnp.int32))
    n_iters = (40 * n_true if n_iters is None
               else jnp.broadcast_to(jnp.asarray(n_iters, jnp.int32), (P,)))
    tenure = (jnp.maximum(4, n_true // 4) if tenure is None
              else jnp.broadcast_to(jnp.asarray(tenure, jnp.int32), (P,)))
    patience = (8 * tenure if patience is None
                else jnp.broadcast_to(jnp.asarray(patience, jnp.int32), (P,)))
    kick_len = (tenure if kick_len is None
                else jnp.broadcast_to(jnp.asarray(kick_len, jnp.int32), (P,)))
    max_iters = int(np.max(np.asarray(n_iters)))
    keys = jax.random.split(jax.random.PRNGKey(seed), P)
    e, s, used = _tabu_batch(J, keys, n_true, n_iters, tenure, patience,
                             kick_len, int(n_restarts), max_iters)
    return (np.asarray(e, dtype=np.float64), np.asarray(s).astype(np.int8),
            np.asarray(used, dtype=np.int64))


def tabu_search_jax(J, n_iters=None, n_restarts: int = 8, tenure=None,
                    seed: int = 0, patience=None, kick_len=None):
    """Drop-in JAX counterpart of ``tabu_search`` (best-of-restarts view).

    J: (n, n) or (P, n, n). Returns (best_energy, best_sigma) — scalars /
    (n,) for a single problem, (P,) / (P, n) for a batch. sigma is int8.
    """
    single = np.ndim(J) == 2
    e, s, _ = tabu_search_jax_runs(J, n_iters=n_iters, n_restarts=n_restarts,
                                   tenure=tenure, seed=seed,
                                   patience=patience, kick_len=kick_len)
    best = np.argmin(e, axis=1)
    best_e = e[np.arange(e.shape[0]), best]
    best_s = s[np.arange(e.shape[0]), best]
    if single:
        return float(best_e[0]), best_s[0]
    return best_e, best_s
