"""64-bit LFSR spin initializer (paper §II.C).

The chip seeds spins from a 64-bit linear feedback shift register; an external
CLK_INIT pulse shifts the LFSR by ONE bit per solve, so consecutive runs see
strongly-correlated-but-distinct initial configurations. We reproduce that
exactly (Fibonacci form, maximal-length taps x^64 + x^63 + x^61 + x^60 + 1)
and generalize to N != 64 by reading the low N bits (N <= 64) or by
concatenating independently-seeded LFSRs per 64-spin tile (N > 64).

Where things are built: the LFSR states are stepped on the host, in numpy,
for many seeds at once (``lfsr64_state_table``, the one recurrence here).
``lfsr_spin_inits`` / ``lfsr_voltage_inits`` unpack them into spins and
voltages on the host. ``IsingMachine.solve`` instead ships the states to
the device as uint32 words (``lfsr_state_words``, 8 bytes per run and
tile) and expands them there into the float32 voltages
(``expand_voltage_inits``), bit for bit what ``lfsr_voltage_inits`` gives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_TAPS_64 = (63, 62, 60, 59)  # bit indices (0-based) of x^64+x^63+x^61+x^60+1
_ZERO_SEED = 0xACE1_BEEF_DEAD_F00D


def lfsr64_state_table(seeds, num_states: int) -> np.ndarray:
    """(len(seeds), num_states) uint64: ``num_states`` consecutive LFSR
    states from each seed, stepped for all seeds at once.

    state[k+1] = (state[k] << 1) | feedback, feedback = XOR of tap bits.
    A zero seed is mapped to the canonical nonzero seed 0xACE1...
    """
    state = np.array([int(s) for s in seeds], dtype=np.uint64)
    state[state == 0] = _ZERO_SEED
    # Step j's feedback reads tap bits 63, 62, 60, 59 of the state j steps
    # on; for j < 60 those are bits 63-j, 62-j, 60-j, 59-j of `state`
    # itself. So bit 63-j of g = XOR over taps of (state << (63 - tap)) is
    # step j's feedback, and the state k <= 60 steps on is
    # (state << k) | (g >> (64 - k)): one numpy pass advances 60 steps.
    block = min(_TAPS_64) + 1
    k = np.arange(block, dtype=np.uint64)
    n_blocks = -(-num_states // block)
    out = np.empty((len(state), n_blocks * block), dtype=np.uint64)
    for b in range(n_blocks):
        g = np.zeros_like(state)
        for t in _TAPS_64:
            g ^= state << np.uint64(63 - t)
        # g >> (63 - k) >> 1: no shift by 64 at k = 0
        out[:, b * block:(b + 1) * block] = (
            (state[:, None] << k) | (g[:, None] >> (np.uint64(63) - k)
                                     >> np.uint64(1)))
        state = (state << np.uint64(block)) | (g >> np.uint64(64 - block))
    return out[:, :num_states]


def lfsr64_states(seed: int, num_states: int) -> np.ndarray:
    """Return ``num_states`` consecutive 64-bit LFSR states (uint64)."""
    return lfsr64_state_table([seed], num_states)[0]


def bits_from_states(states: np.ndarray, n_bits: int) -> np.ndarray:
    """Unpack the low ``n_bits`` of each uint64 state -> (..., n_bits) {0,1}."""
    n = min(n_bits, 64)
    shifts = np.arange(n, dtype=np.uint64)
    bits = (states[..., None] >> shifts) & np.uint64(1)
    return bits.astype(np.int8)


def _tile_states(seeds, n_spins: int, num_runs: int) -> np.ndarray:
    """(len(seeds), tiles, num_runs) uint64: each 64-spin tile's LFSR,
    seeded by splitmix64(seed + tile), as a multi-die array's per-die LFSRs."""
    tiles = -(-n_spins // 64)
    tile_seeds = [_splitmix64(int(s) + t) for s in seeds for t in range(tiles)]
    return lfsr64_state_table(tile_seeds, num_runs).reshape(
        len(seeds), tiles, num_runs)


def _lfsr_bits(n_spins: int, num_runs: int, seed: int) -> np.ndarray:
    """(num_runs, n_spins) {0,1}: tile t gives spins 64t .. 64t+63."""
    states = _tile_states([seed], n_spins, num_runs)[0]     # (tiles, R)
    bits = bits_from_states(states, 64)                      # (tiles, R, 64)
    return bits.transpose(1, 0, 2).reshape(num_runs, -1)[:, :n_spins]


def lfsr_spin_inits(n_spins: int, num_runs: int, seed: int = 0x5EED) -> np.ndarray:
    """(num_runs, n_spins) array of +-1 initial spins, chip-faithful.

    For n_spins > 64, each 64-spin tile gets its own LFSR seeded by
    splitmix64(seed + tile), mirroring a multi-die array with per-die LFSRs.
    """
    return (2 * _lfsr_bits(n_spins, num_runs, seed) - 1).astype(np.int8)


def voltage_levels(vdd: float = 1.0, swing: float = 0.25) -> np.ndarray:
    """The two initial capacitor voltages, for spins -1 and +1:
    vdd/2 -+ swing*vdd/2."""
    spins = np.array([-1, 1], dtype=np.float32)
    return (0.5 + 0.5 * swing * spins) * vdd


def lfsr_voltage_inits(n_spins: int, num_runs: int, seed: int = 0x5EED,
                       vdd: float = 1.0, swing: float = 0.25) -> np.ndarray:
    """Initial capacitor voltages: vdd/2 +- swing*vdd/2 according to LFSR bits."""
    return voltage_levels(vdd, swing)[_lfsr_bits(n_spins, num_runs, seed)]


def lfsr_state_words(seeds, n_spins: int, num_runs: int) -> np.ndarray:
    """(2 * tiles, len(seeds), num_runs) uint32: the LFSR states behind
    ``lfsr_voltage_inits(n_spins, num_runs, seed=s)`` for each s in
    ``seeds``; word 2t holds the low 32 bits of tile t's state, word 2t+1
    the high 32."""
    states = _tile_states(seeds, n_spins, num_runs).transpose(1, 0, 2)
    words = np.empty((states.shape[0], 2) + states.shape[1:], np.uint32)
    words[:, 0] = states & np.uint64(0xFFFF_FFFF)
    words[:, 1] = states >> np.uint64(32)
    return words.reshape(-1, len(seeds), num_runs)


@functools.partial(jax.jit, static_argnums=2)
def expand_voltage_inits(words: jax.Array, levels: jax.Array,
                         n_spins: int) -> jax.Array:
    """(P, R, n_spins) voltages from ``lfsr_state_words`` (2*tiles, P, R):
    spin i reads bit i % 32 of word i // 32 and takes ``levels[bit]``
    (``voltage_levels``, float32)."""
    i = jnp.arange(n_spins, dtype=jnp.uint32)
    word = words[0][..., None]
    for k in range(1, words.shape[0]):
        word = jnp.where(i // 32 == k, words[k][..., None], word)
    bit = (word >> (i % 32)) & 1
    return jnp.where(bit == 1, levels[1], levels[0])


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & ((1 << 64) - 1)
    z = x
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & ((1 << 64) - 1)
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & ((1 << 64) - 1)
    return (z ^ (z >> 31)) or 1
