"""What every load driver shares, and the lookup of a cell's driver.

A traffic mix's ``loop`` names its driver, ``bench/loops/<loop>.py``,
whose ``make(cell, seed, seconds)`` builds it. A driver's ``setup()``
makes every input from the seed and warms every shape the window will
use, ``window(seconds)`` runs the measured window and returns a
:class:`Window`, and ``close()`` frees the program's state. It may offer
``problems_per_s(window)`` (a closed loop), ``latencies(window)`` (an
open loop) and ``notes(window)``; the metric readers use what is there.
Inputs come from the configuration's family,
``bench/families/<family>.py``; the program sees only coupling matrices.
"""
from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from . import manifest

#: seed offsets of the independent random streams one run draws
STREAM_POOL, STREAM_CALLS, STREAM_ORDER, STREAM_WARM = 1, 2, 3, 4


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def instances(config: dict, rng: np.random.Generator, count: int,
              n=None) -> list:
    """``count`` coupling-level matrices of the configuration's family,
    of ``n`` spins (the configuration's ``n`` by default)."""
    family = manifest.code("families", config["family"])
    return family.instances(config, rng, count,
                            int(n if n is not None else config["n"]))


def problems(levels: list) -> list:
    from repro.api import Problem
    return [Problem.from_couplings(J) for J in levels]


def solver(config: dict):
    """The registry entry the configuration names, with its options."""
    from repro.api import get_solver
    return get_solver(config["solver"], **config.get("solver_opts", {}))


CALL_SPAN = "bench.call"
WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def span(name: str, on: bool):
    """The benchmark's own host span, in the profiler's trace."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


@dataclasses.dataclass
class Window:
    start: float
    end: float
    calls: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)
    stats_before: dict = dataclasses.field(default_factory=dict)
    stats_after: dict = dataclasses.field(default_factory=dict)
    lead_in_s: float = 0.0     # uncounted warm-up traffic before the window

    @property
    def elapsed(self) -> float:
        return self.end - self.start


def driver(cell, seed: int, seconds: float):
    """The driver of the cell's traffic mix, found by its ``loop``."""
    return manifest.code("loops", cell.traffic["loop"]).make(
        cell, seed, seconds)
