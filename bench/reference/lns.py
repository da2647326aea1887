"""Plain reference of the checkerboard large-neighbourhood search
(``fabric-jax``): the same operations on the same data, serially.

Per outer sweep and colour class (tile parity), every tile of the class
becomes one die instance: its free spins plus one boundary ancilla whose
coupling row carries the exact field of every clamped spin. All instances
of the class anneal from the same LFSR inits; each tile's candidate is its
lowest-energy run (first on ties), gauge-fixed so the ancilla reads +1,
and is accepted, tile by tile in ascending order, when it lowers the exact
float64 energy against the current state. Where the program spreads the
tiles over its dies does not enter: the answer is the same for any mesh.
"""
from __future__ import annotations

import numpy as np

from . import anneal, lfsr

FABRIC_SWING = 0.25      # init swing of the fabric's sub-anneals
PHASE_SEED_SWEEP = 7919
PHASE_SEED_COLOR = 104729


def tiles(n: int, free_block: int) -> list:
    """Balanced contiguous blocks of at most ``free_block`` spins."""
    k = max(1, -(-n // free_block))
    return [np.asarray(b) for b in np.array_split(np.arange(n), k)]


def work_tiles(n: int, chip_block: int = 64) -> list:
    """Spins of each tile, ancilla included: (free spins + 1) per tile."""
    return [len(b) + 1 for b in tiles(n, chip_block - 1)]


def solve(J_levels, restarts: int, outer_sweeps: int, seed: int,
          inner_runs: int = 8, chip_block: int = 64,
          precision: str = "bfloat16"):
    """Minimize H = -0.5 s'Js. Returns (energies (R,) float64, states
    (R, N) int8, initial energies (R,))."""
    J = np.asarray(J_levels, dtype=np.float64)
    n = J.shape[0]
    rng = np.random.default_rng(seed)
    S = rng.choice([-1.0, 1.0], size=(restarts, n))
    init_e = -0.5 * np.einsum("ri,ij,rj->r", S, J, S)
    F = S @ J                                   # exact float64 fields
    blocks = tiles(n, chip_block - 1)
    n_colors = min(2, len(blocks))
    cb = chip_block
    for sweep in range(outer_sweeps):
        for c in range(n_colors):
            ts = [t for t in range(len(blocks)) if t % n_colors == c]
            batch = np.zeros((len(ts) * restarts, cb, cb), dtype=np.float32)
            for k, t in enumerate(ts):
                lo, hi = int(blocks[t][0]), int(blocks[t][-1]) + 1
                m = hi - lo
                rows = slice(k * restarts, (k + 1) * restarts)
                Jbb = J[lo:hi, lo:hi]
                h = F[:, lo:hi] - S[:, lo:hi] @ Jbb
                batch[rows, 1:m + 1, 1:m + 1] = Jbb
                batch[rows, 0, 1:m + 1] = h
                batch[rows, 1:m + 1, 0] = h
            v0 = lfsr.voltages(
                [seed + PHASE_SEED_SWEEP * (sweep + 1)
                 + PHASE_SEED_COLOR * (c + 1)], cb, inner_runs,
                swing=FABRIC_SWING)
            v0 = np.broadcast_to(v0, (batch.shape[0],) + v0.shape[1:])
            sig, e = anneal.run(batch, v0, precision=precision)
            cand_all = sig[np.arange(len(sig)), e.argmin(axis=1)]
            for k, t in enumerate(ts):
                lo, hi = int(blocks[t][0]), int(blocks[t][-1]) + 1
                m = hi - lo
                cand = cand_all[k * restarts:(k + 1) * restarts]
                cand = (cand[:, 1:m + 1] * cand[:, :1]).astype(np.float64)
                Jbb = J[lo:hi, lo:hi]
                cur = S[:, lo:hi]
                h = F[:, lo:hi] - cur @ Jbb
                e_new = -np.einsum("rm,rm->r", h, cand) \
                    - 0.5 * np.einsum("rm,mk,rk->r", cand, Jbb, cand)
                e_old = -np.einsum("rm,rm->r", h, cur) \
                    - 0.5 * np.einsum("rm,mk,rk->r", cur, Jbb, cur)
                acc = np.flatnonzero(e_new < e_old - 1e-9)
                if len(acc):
                    F[acc] += (cand[acc] - cur[acc]) @ J[lo:hi, :]
                    S[np.ix_(acc, np.arange(lo, hi))] = cand[acc]
    e = -0.5 * np.einsum("ri,ij,rj->r", S, J, S)
    return e, S.astype(np.int8), init_e
