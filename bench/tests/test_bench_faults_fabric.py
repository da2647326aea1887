"""gset2000.fabric driven whole on the CPU at a small size (N=200)."""
import pytest

from bench.tests import faults
from bench.tests.small import run_small

CELL = "gset2000.fabric"


def test_sound_run_is_correct(chip_precision):
    res = run_small(CELL)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"problems_per_s", "setup_s"}


def test_traced_run_reports_the_fabric_ledger(chip_precision):
    res = run_small(CELL, trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert m["fabric.accept_ms"]["value"] > 0
    assert m["fabric.fields_ms"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_anneal_fault_is_caught(chip_precision, monkeypatch, fault):
    from repro.core import engine
    monkeypatch.setattr(engine.AnnealEngine, "run", getattr(faults, fault)(
        engine.AnnealEngine.run))
    res = run_small(CELL)
    assert not res["correct"]
    assert res["checks"]["restarts_unlike_reference"]["value"] > 0


def test_altered_answer_is_caught(chip_precision, monkeypatch):
    from repro.api import registry
    monkeypatch.setattr(registry.FabricSolver, "solve",
                        faults.lower_first_energy(
                            registry.FabricSolver.solve))
    res = run_small(CELL)
    assert not res["correct"]
    assert res["checks"]["energy_vs_spins_gap"]["value"] > 0
    assert res["checks"]["cut_vs_spins_gap"]["value"] > 0
