"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
configuration's file is ``bench/configs/<config>.json``, the mix's
``bench/traffic/<traffic>.json``, each metric's reader
``bench/metrics/<metric>.py``. The data files name the code that serves
them, each a file of its own under a directory of its kind:

* a configuration's ``family`` its input generator, ``bench/families/``;
* its ``work`` the count of the kernel's useful work per call,
  ``bench/work/``;
* its ``check`` the comparison that decides ``correct``, ``bench/checks/``;
* a traffic mix's ``loop`` the load driver, ``bench/loops/``.

Adding one more of any of them is a new file and a new entry; nothing that
exists is edited.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file, plus its entry's name
    traffic: dict         # the traffic mix's file, plus its name
    end_to_end: tuple     # metric entries this cell reports (trace 0)
    per_layer: tuple      # metric entries this cell reports (trace 1)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def reports(metric: dict, cell: str) -> bool:
    """Does ``cell`` report this metric? (No ``workloads`` key: all.)"""
    return "workloads" not in metric or cell in metric["workloads"]


def config_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", f"{name}.json")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


#: the directory of each kind of code a data file can name
KINDS = ("metrics", "checks", "families", "loops", "work")


def code_path(kind: str, name: str) -> str:
    assert kind in KINDS, kind
    return os.path.join(BENCH_DIR, kind, f"{name}.py")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = entries[0]
    config = dict(_read_json(config_path(w["config"])), name=w["config"])
    traffic = dict(_read_json(traffic_path(w["traffic"])),
                   name=w["traffic"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if reports(m, name)))


def load_module(path: str):
    """Import a module by file path (a metric's name carries dots). It is
    registered under a name made from its path, as a dataclass needs."""
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def code(kind: str, name: str):
    """The module of ``kind`` that a data file names, e.g.
    ``code("families", config["family"])``."""
    path = code_path(kind, name)
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} module {name!r} ({path})")
    return load_module(path)
