"""Exact arithmetic the problem families and the checks share.

The generators themselves are the families, one file each,
``bench/families/<family>.py``, found by the configuration's ``family``.
The program receives only coupling matrices (``Problem.from_couplings``),
so a change to the program's own generators cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np


def maxcut_levels(W: np.ndarray) -> np.ndarray:
    """Bias-free Ising couplings of a Max-Cut instance: J = -W."""
    return (-np.asarray(W)).astype(np.int16)


def cut_value(W: np.ndarray, sigma: np.ndarray) -> float:
    """Exact cut of a +-1 assignment: sum over edges i<j of W_ij where
    sigma_i != sigma_j (float64, integer-valued)."""
    s = np.asarray(sigma, dtype=np.float64)
    W = np.asarray(W, dtype=np.float64)
    return float(0.25 * (W.sum() - s @ W @ s))


def ising_energy_f64(J: np.ndarray, sigma: np.ndarray) -> np.ndarray:
    """-0.5 s'Js in float64; J (N, N) or (P, N, N) broadcast against
    sigma (..., N)."""
    s = np.asarray(sigma, dtype=np.float64)
    J = np.asarray(J, dtype=np.float64)
    return -0.5 * np.einsum("...i,...ij,...j->...", s, J, s)
