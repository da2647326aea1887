"""chip64.batch driven whole on the CPU at a small size: sound, it is
correct; with a fault planted under the timed path, it is not."""
import pytest

from bench.tests import faults
from bench.tests.small import run_small

CELL = "chip64.batch"


def test_sound_run_is_correct(chip_precision):
    res = run_small(CELL)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"problems_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_traced_run_reports_per_layer(chip_precision):
    res = run_small(CELL, trace=True)
    assert res["correct"]
    assert "device.idle_pct.offline" in res["metrics"]
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_anneal_fault_is_caught(chip_precision, monkeypatch, fault):
    from repro.core import engine
    monkeypatch.setattr(engine.AnnealEngine, "run", getattr(faults, fault)(
        engine.AnnealEngine.run))
    res = run_small(CELL)
    assert not res["correct"]
    assert res["checks"]["runs_unlike_reference"]["value"] > 0


def test_altered_answer_is_caught(chip_precision, monkeypatch):
    from repro.api import registry
    monkeypatch.setattr(registry.EngineSolver, "solve",
                        faults.flip_one_spin(registry.EngineSolver.solve))
    res = run_small(CELL)
    assert not res["correct"]
    assert res["checks"]["energy_vs_spins_gap"]["value"] > 0
