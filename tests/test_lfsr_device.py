"""LFSR spin initializer and DAC/ADC device model."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.core import (DeviceModel, IsingMachine, lfsr64_states,
                        lfsr_spin_inits, lfsr_voltage_inits)
from repro.core.lfsr import (_splitmix64, expand_voltage_inits,
                             lfsr64_state_table, lfsr_state_words,
                             voltage_levels)

_TAPS = (63, 62, 60, 59)
_GOLDEN = 0x9E3779B97F4A7C15   # splitmix64 maps -_GOLDEN to 0, then to 1


def _states_by_loop(seed: int, n: int) -> list:
    """The recurrence one state at a time, on Python ints."""
    s = seed or 0xACE1_BEEF_DEAD_F00D
    out = []
    for _ in range(n):
        out.append(s)
        fb = 0
        for t in _TAPS:
            fb ^= (s >> t) & 1
        s = ((s << 1) | fb) & ((1 << 64) - 1)
    return out


def _voltages_by_loop(n_spins, runs, seed, vdd, swing):
    """lfsr_voltage_inits from the loop's states: tile t seeded by
    splitmix64(seed + t), low bits first, vdd/2 +- swing*vdd/2 in float32."""
    tiles = []
    for t in range(-(-n_spins // 64)):
        states = _states_by_loop(_splitmix64(seed + t), runs)
        tiles.append([[(s >> b) & 1 for b in range(64)] for s in states])
    bits = np.concatenate(np.asarray(tiles, np.int8), axis=1)[:, :n_spins]
    spins = (2 * bits - 1).astype(np.float32)
    return (0.5 + 0.5 * swing * spins) * vdd


def test_lfsr_deterministic_and_shifting():
    a = lfsr64_states(0xDEAD, 100)
    b = lfsr64_states(0xDEAD, 100)
    assert np.array_equal(a, b)
    # consecutive states: state[k+1] = shift(state[k]) -> strictly different
    assert np.all(a[1:] != a[:-1])


def test_lfsr_no_short_cycles():
    states = lfsr64_states(1, 10_000)
    assert len(np.unique(states)) == 10_000   # maximal-length taps


def test_spin_inits_shape_and_values():
    s = lfsr_spin_inits(64, 50, seed=3)
    assert s.shape == (50, 64)
    assert set(np.unique(s)) <= {-1, 1}
    # consecutive runs differ (one LFSR shift per solve)
    assert np.any(s[0] != s[1])
    # tiling beyond 64 spins
    s2 = lfsr_spin_inits(130, 10, seed=3)
    assert s2.shape == (10, 130)


def test_voltage_inits_levels():
    v = lfsr_voltage_inits(64, 20, seed=1, vdd=1.0, swing=0.5)
    assert set(np.round(np.unique(v), 6)) <= {0.25, 0.75}


def test_quantize_paper_range():
    dev = DeviceModel()
    J = jnp.asarray(np.arange(-15, 16, dtype=np.float32))[None, :] * jnp.eye(31)
    q = dev.quantize(J)
    assert float(jnp.max(q)) <= dev.max_level
    assert float(jnp.min(q)) >= -dev.max_level
    # integer problems in [-15, 15] are unchanged
    rng = np.random.default_rng(0)
    Ji = rng.integers(-15, 16, size=(16, 16)).astype(np.float32)
    np.fill_diagonal(Ji, 0)
    assert np.array_equal(np.asarray(dev.quantize(jnp.asarray(Ji))), Ji)
    assert dev.n_levels == 31


@given(st.floats(0.0, 1.0))
@settings(max_examples=20, deadline=None)
def test_adc_threshold(v):
    dev = DeviceModel()
    out = float(dev.adc(jnp.asarray(v)))
    assert out == (1.0 if v >= 0.5 else -1.0)


def test_timing_constants():
    dev = DeviceModel()
    assert dev.n_steps == int(3.75 * 64 * dev.substeps)
    assert np.isclose(dev.dt * dev.slots_per_sweep * dev.substeps, 1.0)
    from repro.core import anneal_time_seconds
    assert np.isclose(anneal_time_seconds(dev), 3e-6)  # the paper's 3 us


# -- the vectorised states and their expansion on the device -------------------

@pytest.mark.parametrize("seeds", [
    [0], [1], [(1 << 64) - 1], [1 << 63], [0xACE1_BEEF_DEAD_F00D],
    [_splitmix64(-_GOLDEN), _splitmix64(-_GOLDEN - 1), _splitmix64(1 - _GOLDEN)],
    [0, 7, 0xDEAD, 0, 1 << 40, 12345678901234567890],
], ids=["zero", "one", "all-ones", "top-bit", "zero-rule-seed",
        "splitmix-near-zero", "mixed"])
@pytest.mark.parametrize("n", [1, 59, 60, 61, 130, 1000])
def test_state_table_matches_the_loop(seeds, n):
    table = lfsr64_state_table(seeds, n)
    assert table.shape == (len(seeds), n) and table.dtype == np.uint64
    for row, seed in zip(table, seeds):
        expect = np.array(_states_by_loop(seed, n), dtype=np.uint64)
        assert np.array_equal(row, expect)
        assert np.array_equal(lfsr64_states(seed, n), expect)


_DEFAULT = DeviceModel()


@pytest.mark.parametrize("vdd,swing", [(_DEFAULT.vdd, _DEFAULT.init_swing),
                                       (1.2, 0.3)], ids=["default", "other"])
@pytest.mark.parametrize("n", [16, 63, 64, 130])
def test_device_expanded_v0_is_the_stacked_host_v0(n, vdd, swing):
    runs = 70
    # base 0 and a base whose first tile splitmix maps to the zero rule
    for base in (0, (1 << 64) - _GOLDEN):
        seeds = [base + 7919 * p for p in range(3)]
        words = lfsr_state_words(seeds, n, runs)
        assert words.dtype == np.uint32
        assert words.shape == (2 * -(-n // 64), 3, runs)
        v0 = expand_voltage_inits(
            words, voltage_levels(vdd, swing).astype(np.float32), n)
        host = np.stack([lfsr_voltage_inits(n, runs, seed=s, vdd=vdd,
                                            swing=swing) for s in seeds])
        assert v0.dtype == host.dtype == np.float32
        assert np.array_equal(np.asarray(v0), host)
        loop = np.stack([_voltages_by_loop(n, runs, s, vdd, swing)
                         for s in seeds])
        assert np.array_equal(host, loop)


def test_machine_solve_is_the_anneal_of_the_host_v0():
    """The scan path from the device-expanded inits gives the sigma,
    energies and voltages of the same anneal from the stacked host inits."""
    rng = np.random.default_rng(4)
    P, N, R, seed = 2, 20, 8, 11
    J = rng.integers(-15, 16, size=(P, N, N)).astype(np.float32)
    J = np.triu(J, 1)
    J = J + J.transpose(0, 2, 1)
    m = IsingMachine(backend="jnp")
    out = m.solve(J, num_runs=R, seed=seed, quantize=False)
    dev = dataclasses.replace(m.device, n_spins=N)
    v0 = np.stack([lfsr_voltage_inits(N, R, seed=seed + 7919 * p,
                                      vdd=dev.vdd, swing=dev.init_swing)
                   for p in range(P)])
    ref = m.engine.run(jnp.asarray(J), v0)
    assert np.array_equal(out.sigma, np.asarray(ref.sigma))
    assert np.array_equal(out.energy, np.asarray(ref.energy))
    assert np.array_equal(out.v_final, np.asarray(ref.v_final))
