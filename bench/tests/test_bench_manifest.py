"""BENCHMARK.json against the benchmark's contract and the files it names.

Runs on the CPU: it reads files only."""
import json
import os
import re

import pytest

from bench.lib import manifest

BENCH = manifest.load()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
LINE_RE = re.compile(r"^[^\n\t]{1,200}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
    cmd, paths = BENCH["command"], BENCH["paths"]
    assert 1 <= len(cmd) <= 32 and all(LINE_RE.match(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH_RE.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(manifest.ROOT, p))
    for word in cmd:
        if "/" in word:
            assert not word.startswith("/") and ".." not in word
            assert any(word.startswith(p.rstrip("/") + "/") for p in paths)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_resolves(name):
    c = next(c for c in BENCH["configs"] if c["name"] == name)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert manifest.NAME_RE.match(name)
    assert c["file"] == os.path.relpath(manifest.config_path(name),
                                        manifest.ROOT)
    with open(manifest.config_path(name)) as f:
        cfg = json.load(f)
    assert hasattr(manifest.code("checks", cfg["check"]), "check")
    assert hasattr(manifest.code("families", cfg["family"]), "instances")
    assert hasattr(manifest.code("work", cfg["work"]), "call_instances")
    assert all(manifest.NAME_RE.match(k) for k in c["reduced"])
    assert len(c["reduced"]) <= 16
    assert LINE_RE.match(c["source"]) and LINE_RE.match(c["why"])
    assert any(w["config"] == name for w in BENCH["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    for key in ("name", "config", "traffic"):
        assert manifest.NAME_RE.match(w[key])
    assert LINE_RE.match(w["why"])
    cell = manifest.cell(name, BENCH)
    assert hasattr(manifest.code("loops", cell.traffic["loop"]), "make")
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_metric_names_units_and_reader(name):
    m = next(m for m in METRICS if m["name"] == name)
    assert manifest.NAME_RE.match(name)
    assert manifest.UNIT_RE.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert hasattr(manifest.code("metrics", name), "read")
    for cell in m.get("workloads", []):
        assert cell in CELLS


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_entries():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_moves_is_reported(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert LINE_RE.match(m["layer"])
    moves = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert manifest.reports(moves, cell), (name, cell)


def test_four_chip_cells_within_allowance():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
