"""closed: one client calling the configuration's registry entry back to
back, ``problems_per_call`` problems a call, cycling through a pool of
``pool`` inputs made at set-up. Every call has the pool's one shape, so
one call at set-up warms it all.

Traffic keys: ``problems_per_call``, ``runs``, ``pool``, ``trace_seconds``
and the check's ``check_sample``.
"""
from __future__ import annotations

import dataclasses
import time

from bench.lib.drive import (CALL_SPAN, STREAM_CALLS, STREAM_POOL, Window,
                             instances, problems, rng_for, solver, span)


@dataclasses.dataclass
class Call:
    """One call: which pool entry, its seed, the report."""
    pool_index: int
    seed: int
    report: object


class ClosedLoop:
    """One client calling ``get_solver(config.solver).solve`` back to back
    on a pool of inputs, ``problems_per_call`` problems per call. The
    window runs whole calls: it starts at the first timed call and ends
    when the first call to finish after ``seconds`` completes."""

    def __init__(self, cell, seed: int):
        self.cell = cell
        self.seed = int(seed)
        self.config, self.traffic = cell.config, cell.traffic
        self.runs = int(self.traffic["runs"])
        self.per_call = int(self.traffic["problems_per_call"])
        self.base_seed = int(rng_for(seed, STREAM_CALLS).integers(0, 2**31))

    def call_seed(self, k: int) -> int:
        return self.base_seed + k

    def setup(self) -> None:
        from repro.api import ProblemSuite
        t0 = time.perf_counter()
        rng = rng_for(self.seed, STREAM_POOL)
        self.pool = []
        for _ in range(int(self.traffic["pool"])):
            levels = instances(self.config, rng, self.per_call)
            self.pool.append((levels, ProblemSuite(problems(levels))))
        self.solver = solver(self.config)
        t1 = time.perf_counter()
        # every call has the pool's one shape: one call compiles it all
        self.solver.solve(self.pool[0][1], runs=self.runs,
                          seed=self.call_seed(-1))
        self.setup_phases = {"inputs": t1 - t0,
                             "warm": time.perf_counter() - t1}

    def window(self, seconds: float, traced: bool = False) -> Window:
        w = Window(start=time.perf_counter(), end=0.0)
        while w.end - w.start < seconds:
            k = len(w.calls)
            p, s = k % len(self.pool), self.call_seed(k)
            with span(CALL_SPAN, traced):
                rep = self.solver.solve(self.pool[p][1], runs=self.runs,
                                        seed=s)
            w.end = time.perf_counter()
            w.calls.append(Call(p, s, rep))
        return w

    def attempted(self, w: Window) -> int:
        return len(w.calls) * self.per_call

    def problems_per_s(self, w: Window) -> float:
        return self.attempted(w) / w.elapsed

    def close(self) -> None:
        self.solver = None


def make(cell, seed: int, seconds: float) -> ClosedLoop:
    return ClosedLoop(cell, seed)
