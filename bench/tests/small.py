"""Cells cut to a size a CPU test run can hold, for the tests that drive
a whole run without the chip. Only counts, sizes and rates change; the
configuration's family, precision, solver and check stay the cell's own."""
from __future__ import annotations

import dataclasses

from bench.lib import manifest

#: (configuration, traffic mix, chips) of each cell the tests drive; the
#: files are the benchmark's own, so a cell runs here even where
#: BENCHMARK.json does not list it
CELLS = {
    "chip64.batch": ("chip64", "batch", 1),
    "gset2000.fabric": ("gset2000", "fabric", 1),
    "gset2000-4die.fabric": ("gset2000-4die", "fabric", 4),
    "chip64.stream": ("chip64", "stream", 1),
}

SMALL = {
    "chip64.batch": ({}, {"problems_per_call": 4, "runs": 32, "pool": 2,
                          "trace_seconds": 0.5,
                          "check_sample": {"calls": 2, "problems": 3}}),
    "gset2000.fabric": ({"n": 256, "edges": 5000},
                        {"runs": 4, "pool": 2, "trace_seconds": 0.5}),
    "gset2000-4die.fabric": ({"n": 200, "edges": 1999},
                             {"runs": 2, "pool": 2, "trace_seconds": 0.5}),
    "chip64.stream": ({}, {"rate_per_s": 40, "runs": 16, "lead_in_s": 0.2,
                           "service": {"max_batch": 4, "max_wait_s": 0.02},
                           "warm_batch_sizes": [1, 4], "grace_s": 30,
                           "trace_seconds": 0.5,
                           "check_sample": {"requests": 3}}),
}


def small_cell(name: str, chips: int = 1):
    config, traffic, _ = CELLS[name]
    bench = {"workloads": [{"name": name, "config": config,
                            "traffic": traffic, "chips": chips}],
             "end_to_end": manifest.load()["end_to_end"],
             "per_layer": manifest.load()["per_layer"]}
    cell = manifest.cell(name, bench)
    cfg, trf = SMALL[name]
    return dataclasses.replace(cell, config={**cell.config, **cfg},
                               traffic={**cell.traffic, **trf})


def run_small(name: str, seed: int = 2**31 + 977, trace: bool = False,
              seconds: float = 0.5, chips: int = 1) -> dict:
    """One whole run of a small cell on the CPU: the harness's look for a
    chip skipped, everything else as on the chip."""
    from bench import run
    return run.execute(name, seed, seconds, trace, require_tpu=False,
                       cell=small_cell(name, chips))
