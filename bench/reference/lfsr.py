"""The die's 64-bit LFSR spin initializer, vectorized over seeds.

Fibonacci form with taps x^64 + x^63 + x^61 + x^60 + 1, one shift per run;
a tile of N <= 64 spins reads the low N bits of the state, and the state
is seeded by splitmix64(seed) (paper section II.C). A zero state maps to
the canonical nonzero seed, as on the die.
"""
from __future__ import annotations

import numpy as np

_TAPS = (63, 62, 60, 59)
_MASK = (1 << 64) - 1
_ZERO_SEED = 0xACE1_BEEF_DEAD_F00D


def splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _MASK
    z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK
    return (z ^ (z >> 31)) or 1


def spins(seeds, n: int, runs: int) -> np.ndarray:
    """(len(seeds), runs, n) int8 +-1 initial spins, one LFSR per seed
    (n <= 64: one tile)."""
    if n > 64:
        raise ValueError(f"one LFSR tile holds at most 64 spins, got {n}")
    state = np.array([splitmix64(int(s)) or _ZERO_SEED for s in seeds],
                     dtype=np.uint64)
    shifts = np.arange(n, dtype=np.uint64)
    out = np.empty((len(state), runs, n), dtype=np.int8)
    one = np.uint64(1)
    for r in range(runs):
        out[:, r] = ((state[:, None] >> shifts) & one).astype(np.int8)
        fb = np.zeros_like(state)
        for t in _TAPS:
            fb ^= (state >> np.uint64(t)) & one
        state = (state << one) | fb
    return (2 * out - 1).astype(np.int8)


def voltages(seeds, n: int, runs: int, swing: float,
             vdd: float = 1.0) -> np.ndarray:
    """Initial capacitor voltages vdd/2 +- swing*vdd/2 by the LFSR bits,
    float32 (P, runs, n)."""
    s = spins(seeds, n, runs).astype(np.float32)
    return ((np.float32(0.5) + np.float32(0.5 * swing) * s)
            * np.float32(vdd)).astype(np.float32)
