"""chip64.gd-batch driven whole on the CPU at a small size: sound, it is
correct; with a fault planted under the timed path, it is not; and its
control comes out correct at the stated arithmetic and not correct with
the voltages held one precision below it. Also its own metric's reader,
``kernel.int8_pct``, on made-up traces."""
import dataclasses
import types

import pytest

from bench import control_unit
from bench.lib import manifest
from bench.tests import faults
from bench.trace import Reduced

CELL = "chip64.gd-batch"
SMALL_TRAFFIC = {"problems_per_call": 4, "runs": 32, "pool": 2,
                 "trace_seconds": 0.5,
                 "check_sample": {"calls": 2, "problems": 4}}


def small_cell():
    """The cell as BENCHMARK.json has it, 4 problems x 32 runs a call."""
    bench = manifest.load()
    cell = manifest.cell(CELL, bench)
    return dataclasses.replace(cell,
                               traffic={**cell.traffic, **SMALL_TRAFFIC})


def run_small(seed: int = 2**31 + 977) -> dict:
    from bench import run
    return run.execute(CELL, seed, 0.5, False, require_tpu=False,
                       cell=small_cell())


def test_cell_runs_the_gd_engine_on_int8():
    cell = small_cell()
    assert cell.config["solver"] == "engine"
    assert cell.config["solver_opts"] == {"variant": "gd"}
    assert cell.config["precision"] == "int8"
    assert cell.traffic["loop"] == "closed"


def test_sound_run_is_correct():
    res = run_small()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"problems_per_s", "setup_s"}
    assert {c["value"] for c in res["checks"].values()} == {0.0}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_anneal_fault_is_caught(monkeypatch, fault):
    from repro.core import engine
    monkeypatch.setattr(engine.AnnealEngine, "run", getattr(faults, fault)(
        engine.AnnealEngine.run))
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["runs_unlike_reference"]["value"] > 0


def test_altered_answer_is_caught(monkeypatch):
    from repro.api import registry
    monkeypatch.setattr(registry.EngineSolver, "solve",
                        faults.flip_one_spin(registry.EngineSolver.solve))
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["energy_vs_spins_gap"]["value"] > 0


@pytest.mark.parametrize("state,correct",
                         [("float32", True),
                          (control_unit.CONTROL_STATE, False)])
def test_control(state, correct):
    restore = control_unit.install(state)
    try:
        res = run_small()
    finally:
        restore()
    assert res["correct"] is correct, res["checks"]
    if not correct:
        assert res["checks"]["runs_unlike_reference"]["value"] > 0


@pytest.mark.parametrize("ops,share", [
    ([("fused_anneal_kernel_int8.1", 30), ("copy.4", 10)], 100.0),
    ([("fused_anneal_kernel_int8.1", 30), ("fused_anneal_kernel.1", 10)],
     75.0),
    ([("fused_anneal_kernel.1", 30)], 0.0),     # the int8 kernel unnamed
    ([("copy.4", 10)], None),                   # no anneal kernel at all
])
def test_int8_share_reader(ops, share):
    t, timeline = 0, []
    for name, ns in ops:
        timeline.append((t, t + ns, name))
        t += ns
    trace = Reduced(lo=0, hi=t, devices=[timeline], calls=[(0, t)], host=[])
    reader = manifest.code("metrics", "kernel.int8_pct")
    assert reader.read(types.SimpleNamespace(trace=trace)) == share
    assert reader.read(types.SimpleNamespace(trace=None)) is None
