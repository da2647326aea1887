"""Ising / QUBO energy functions and problem mappings (paper Eq. 1-2).

Conventions
-----------
* ``J`` is a full (..., N, N) coupling matrix with zero diagonal. Problems are
  generated symmetric (J_ij == J_ji); the chip is *directed* so the simulator
  accepts arbitrary J and uses row i as the input couplings of node i.
* Spins ``sigma`` are +-1 with shape (..., N).
* Energy is the bias-free Ising Hamiltonian of Eq. (1)/(5):

      H = - sum_{i<j} J_ij s_i s_j  =  -0.5 * s^T J s        (zero diagonal)

  For directed J the effective symmetric coupling is (J + J^T)/2, which is
  exactly what -0.5 s^T J s computes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Energies and cut weights are integer sums of DAC levels (up to the
#: large boundary-field couplings of decomposition sub-instances) and must
#: come out exact. A TPU's default f32 matmul rounds its operands to bf16,
#: which loses integers above 256, so these contractions run at full f32.
EXACT = jax.lax.Precision.HIGHEST


def ising_energy(J, sigma):
    """Bias-free Ising energy, batched with broadcasting.

    J: (..., N, N) float; sigma: (..., N) +-1 with any leading axes that
    broadcast against J's batch axes (e.g. J (P,N,N), sigma (P,R,N)).
    Returns broadcast-batch energy.
    """
    s = jnp.asarray(sigma, dtype=J.dtype)
    Js = local_field(J, s)
    return -0.5 * jnp.sum(s * Js, axis=-1)


def local_field(J, sigma):
    """f_i = sum_j J_ij s_j — the net coupling drive seen by node i.
    Broadcasts: sigma (..., R, N) against J (..., N, N)."""
    s = jnp.asarray(sigma, dtype=J.dtype)
    return jnp.matmul(s, jnp.swapaxes(J, -1, -2), precision=EXACT)


def flip_deltas(J, sigma):
    """Energy change for flipping each spin: dH_k = 2 s_k f_k (symmetric J)."""
    return 2.0 * sigma.astype(J.dtype) * local_field(J, sigma)


# --------------------------------------------------------------------------
# QUBO <-> Ising maps
# --------------------------------------------------------------------------

def qubo_to_ising(Q):
    """Map QUBO  min x^T Q x  (x in {0,1}^N, Q symmetric) to Ising (J, h, c).

    With x = (s + 1)/2:
        x^T Q x = 0.25 * s^T Q s + 0.5 * (Q 1)^T s + const
    Ising form  H = -sum_{i<j} J_ij s_i s_j - sum_i h_i s_i + c  gives
        J = -Q/2 (off-diagonal), h = -0.5 * (row_sums + diag), and a constant.
    Returns (J, h, const) such that  x^T Q x == -0.5 s^T J s - h . s + const.
    """
    Q = np.asarray(Q, dtype=np.float64)
    n = Q.shape[-1]
    Qs = 0.5 * (Q + Q.T)
    offdiag = Qs - np.diag(np.diag(Qs))
    J = -0.5 * offdiag
    row = Qs.sum(axis=1)  # includes diagonal
    h = -0.5 * row
    const = 0.25 * offdiag.sum() + 0.5 * np.trace(Qs) + 0.25 * 2 * 0  # see below
    # const: x^T Q x at s: 0.25*sum_ij Qs_ij (s_i s_j + s_i + s_j + 1)
    #      = 0.25 s'Qs s + 0.5 (Qs 1).s + 0.25 * Qs.sum()
    # and 0.25 s'Qs s = 0.25 * (s' offdiag s) + 0.25 * trace(Qs)
    const = 0.25 * Qs.sum() + 0.25 * np.trace(Qs)
    return J, h, const


def maxcut_to_ising(W):
    """Max-Cut -> bias-free Ising per paper Eq. (2):  J = -W.

    cut(s) = 0.25 * sum_ij W_ij (1 - s_i s_j) = const - 0.5*sum_{i<j} W_ij s_i s_j
    so maximizing the cut == minimizing H with J = -W.
    """
    W = np.asarray(W, dtype=np.float64)
    J = -(W - np.diag(np.diag(W)))
    return J


def absorb_fields(J, h):
    """Fold bias fields into one ancilla spin (the chip is bias-free).

    Returns J' of shape (N+1, N+1) with J'_{0,i} = J'_{i,0} = h_i. In the
    gauge s_0 = +1 the (N+1)-spin bias-free Hamiltonian equals the original
    H = -0.5 s'Js - h.s; if a solver returns s_0 = -1, flip the whole
    configuration (global Z2 symmetry) before reading out x = (s+1)/2.
    """
    J = np.asarray(J, dtype=np.float64)
    h = np.asarray(h, dtype=np.float64)
    n = J.shape[-1]
    out = np.zeros((n + 1, n + 1), dtype=np.float64)
    out[1:, 1:] = J
    out[0, 1:] = h
    out[1:, 0] = h
    return out


def fix_gauge(sigma):
    """Flip configurations whose ancilla spin (index 0) is -1."""
    s = jnp.asarray(sigma)
    return s * s[..., :1]


def maxcut_value(W, sigma):
    """Cut weight for +-1 partition sigma."""
    W = jnp.asarray(W)
    s = jnp.asarray(sigma, dtype=W.dtype)
    total = jnp.sum(jnp.triu(W, k=1))
    sWs = 0.5 * jnp.einsum("...i,ij,...j->...", s, W, s,   # sum_{i<j} W s s
                           precision=EXACT)
    return 0.5 * (total - sWs)
