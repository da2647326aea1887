"""chip64.stream driven whole on the CPU at a small size (40 requests/s,
flushes of at most 4)."""
from bench.tests import faults
from bench.tests.small import run_small

CELL = "chip64.stream"


def test_sound_run_is_correct(chip_precision):
    res = run_small(CELL)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"request_p95_s", "setup_s"}
    assert res["attempted"] == 20
    assert res["notes"]["programs_made_ready"]["window"] == 0
    assert res["notes"]["lead_in_answers_wait_s"] >= 0


def test_traced_run_reports_the_service(chip_precision):
    res = run_small(CELL, trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert m["serve.flush_size"]["value"] >= 1
    assert m["registry.compiles.stream"]["value"] == 0


def test_state_unchanged_is_caught(chip_precision, monkeypatch):
    # (half of a flush left out is not among the stream's faults: the
    # service's float64 validation sees the wrong rows and bisects the
    # flush until every answer it hands out is right)
    from repro.core import engine
    monkeypatch.setattr(engine.AnnealEngine, "run", faults.state_unchanged(
        engine.AnnealEngine.run))
    res = run_small(CELL)
    assert not res["correct"]
    assert res["checks"]["runs_unlike_reference"]["value"] > 0


def test_altered_answer_is_caught(chip_precision, monkeypatch):
    from repro.serve import service
    monkeypatch.setattr(service.IsingService, "_deliver",
                        faults.flip_served_spin(
                            service.IsingService._deliver))
    res = run_small(CELL)
    assert not res["correct"]
