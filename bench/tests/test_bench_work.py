"""The anneal's operation count: real runs on real spins only."""
import math

import pytest

from bench.lib import manifest
from bench.reference import lns
from bench.work import anneal as work


def test_chip64_hand_count():
    # 256 problems x 1024 runs x 1920 steps x 2 * 64^2 operations
    ops, _ = work.batch([(64, 1024)] * 256, 1920, "bfloat16")
    assert ops == 256 * 1024 * 1920 * 2 * 64 * 64 == 4123168604160


@pytest.mark.parametrize("block_r", [8, 64, 128, 256])
@pytest.mark.parametrize("lanes", [64, 128])
def test_count_ignores_padding(block_r, lanes):
    """A kernel's run block and lane padding change its time, never the
    work: the count takes the real (n, runs) and nothing else."""
    runs = 1000                                   # not a multiple of 8
    padded_runs = math.ceil(runs / block_r) * block_r
    assert padded_runs >= runs and lanes >= 64
    ops, byt = work.batch([(64, runs)], 1920, "bfloat16")
    assert ops == work.anneal_ops(64, 1920, runs)
    assert byt == 64 * 64 * 2 + 8 * 64 * runs


@pytest.mark.parametrize("n", [200, 2000, 2001])
def test_fabric_tiles_match_reference(n):
    inst = work.fabric_instances(n, restarts=4, inner_runs=8, die_spins=64,
                                 outer_sweeps=64)
    assert sorted(m for m, _ in inst) == sorted(lns.work_tiles(n, 64))
    assert all(r == 4 * 8 * 64 for _, r in inst)
    assert sum(m - 1 for m, _ in inst) == n


def test_least_time_names_its_bound():
    t, bound = work.least_time(197e12, 1.0, 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_time(1.0, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")


def test_die_calls_are_the_hand_count():
    calls = manifest.code("work", "die")
    inst = calls.call_instances({"n": 64}, {"runs": 1024,
                                           "problems_per_call": 256})
    ops, _ = work.batch(inst, 1920, "bfloat16")
    assert ops == 4123168604160


@pytest.mark.parametrize("n", [200, 2000])
def test_fabric_calls_count_every_sweep(n):
    calls = manifest.code("work", "fabric")
    cfg = {"n": n, "die_spins": 64, "solver_opts": {"inner_runs": 8}}
    inst = calls.call_instances(cfg, {"runs": 4})
    sweeps = max(4, 2 * math.ceil(n / 63))
    assert inst == work.fabric_instances(n, 4, 8, 64, sweeps)
