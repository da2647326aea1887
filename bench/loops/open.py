"""open: scheduled arrivals into ``IsingService`` at a fixed rate.

One sender thread submits each request at its due time; the caller's
thread collects the answers in order of sending. Every seed offers the
same multiset of sizes (``n_min``..``n_max`` in turn) and inter-arrival
gaps (exponential quantiles at ``rate_per_s``), in its own order, each
request a distinct problem of the configuration's family.

Before the window, ``lead_in_s`` of other requests at the same rate bring
the service to its steady state; the window opens once every lead-in
request has been answered, so nothing left from set-up runs inside it.
The lead-in, and the wait for its answers, is set-up.

Traffic keys: ``rate_per_s``, ``lead_in_s``, ``n_min``, ``n_max``,
``runs``, ``deadline_s``, ``service`` (``IsingService`` arguments),
``warm_batch_sizes`` (the flush sizes warmed at set-up), ``grace_s``
(how long past the last arrival an answer is waited for),
``trace_seconds`` and the check's ``check_sample``.
"""
from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import Optional

import numpy as np

from bench.lib.drive import (STREAM_CALLS, STREAM_ORDER, STREAM_POOL,
                             STREAM_WARM, WINDOW_SPAN, Window, instances,
                             problems, rng_for, solver, span)

#: the service's supervision counters whose changes a run's notes report
RESILIENCE = ("retries", "bisections", "hedges", "flush_timeouts",
              "validation_failures", "fallback_solves", "failed_requests",
              "breaker_trips")


@dataclasses.dataclass
class Request:
    index: int
    n: int
    due: float            # scheduled send time (perf_counter seconds)
    sent: float = math.nan
    done: float = math.nan
    result: object = None
    error: Optional[str] = None


def _sizes(lo: int, hi: int, count: int) -> np.ndarray:
    return lo + np.arange(count) % (hi - lo + 1)


class OpenLoop:
    def __init__(self, cell, seed: int, seconds: float):
        self.cell = cell
        self.seed = int(seed)
        self.config, self.traffic = cell.config, cell.traffic
        self.rate = float(self.traffic["rate_per_s"])
        self.runs = int(self.traffic["runs"])
        self.deadline_s = float(self.traffic["deadline_s"])
        self.count = max(1, int(round(self.rate * seconds)))
        self.lead = int(round(self.rate * float(
            self.traffic.get("lead_in_s", 0.0))))
        self.service_seed = int(rng_for(seed, STREAM_CALLS)
                                .integers(0, 2**31))
        self.phase_notes: dict = {}

    def setup(self) -> None:
        from repro.api import ProblemSuite
        from repro.serve import IsingService
        t0 = time.perf_counter()
        t = self.traffic
        order = rng_for(self.seed, STREAM_ORDER)
        lo, hi = int(t["n_min"]), int(t["n_max"])
        self.sizes = order.permutation(_sizes(lo, hi, self.count))
        q = (np.arange(self.count) + 0.5) / self.count
        self.gaps = order.permutation(-np.log1p(-q) / self.rate)
        rng = rng_for(self.seed, STREAM_POOL)
        levels = [None] * self.count
        for n in np.unique(self.sizes):
            idx = np.flatnonzero(self.sizes == n)
            for i, J in zip(idx, instances(self.config, rng, len(idx), n)):
                levels[i] = J
        self.levels = levels
        self.problems = problems(levels)
        lead_sizes = _sizes(lo, hi, self.lead)
        self.lead_problems = [p for n in np.unique(lead_sizes) for p in
                              problems(instances(
                                  self.config, rng_for(self.seed,
                                                       STREAM_WARM + 1),
                                  int(np.sum(lead_sizes == n)), n))]
        order.shuffle(self.lead_problems)
        t1 = time.perf_counter()
        # warm every flush size the window can produce: the engine compiles
        # its kernel once per batch size
        warm = solver(self.config)
        wrng = rng_for(self.seed, STREAM_WARM)
        lo_b, hi_b = t["warm_batch_sizes"]
        for b in range(int(lo_b), int(hi_b) + 1):
            suite = ProblemSuite(problems(instances(self.config, wrng, b)))
            warm.solve(suite, runs=self.runs, seed=self.service_seed,
                       budget=self.deadline_s)
        self.service = IsingService(
            solver=self.config["solver"], runs=self.runs,
            seed=self.service_seed, **t["service"],
            **self.config.get("solver_opts", {}))
        self.service.start()
        for p in problems(instances(self.config, wrng, 4)):
            self.service.submit(p, deadline_s=self.deadline_s).result()
        self.setup_phases = {"inputs": t1 - t0,
                             "warm": time.perf_counter() - t1}

    def _resilience(self) -> dict:
        r = self.service.stats()["resilience"]
        return {k: r[k] for k in RESILIENCE}

    def window(self, seconds: float, traced: bool = False) -> Window:
        count = min(self.count, max(1, int(round(self.rate * seconds))))
        due = np.cumsum(self.gaps[:count]) - self.gaps[0]
        reqs = [Request(i, int(self.sizes[i]), 0.0) for i in range(count)]
        lead_tickets: queue.SimpleQueue = queue.SimpleQueue()
        posted: queue.SimpleQueue = queue.SimpleQueue()
        opened = threading.Event()
        w = Window(start=0.0, end=0.0)
        grace = float(self.traffic["grace_s"])
        resilience = [self._resilience()]
        first = time.perf_counter() + 0.05
        lead_at = first + np.arange(len(self.lead_problems)) / self.rate

        def send():
            # lead-in: the same rate before the window, so the window
            # opens on a service already in its steady state
            for i, p in enumerate(self.lead_problems):
                delay = lead_at[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                try:
                    lead_tickets.put(self.service.submit(
                        p, deadline_s=self.deadline_s))
                except Exception:        # not counted: warm-up traffic
                    lead_tickets.put(None)
            opened.wait()
            for i, r in enumerate(reqs):
                r.due = w.start + due[i]
                delay = r.due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                r.sent = time.perf_counter()
                ticket = None
                try:
                    ticket = self.service.submit(
                        self.problems[i], deadline_s=self.deadline_s)
                except Exception as e:      # shed or refused: a failure
                    r.error = f"{type(e).__name__}: {e}"
                posted.put(ticket)

        sender = threading.Thread(target=send, name="bench-client")
        sender.start()
        try:
            for _ in self.lead_problems:
                ticket = lead_tickets.get()
                try:
                    if ticket is not None:
                        ticket.result(timeout=grace)
                except Exception:
                    pass
            lead_end = float(lead_at[-1]) if len(lead_at) else first
            self.phase_notes["lead_in_answers_wait_s"] = max(
                0.0, time.perf_counter() - lead_end)
            resilience.append(self._resilience())
            w.stats_before = self.service.stats()
            w.start = time.perf_counter() + 0.01
        finally:
            opened.set()
        w.lead_in_s = w.start - first
        with span(WINDOW_SPAN, traced):
            give_up = w.start + due[-1] + grace
            for r in reqs:               # answers in order of sending
                ticket = posted.get()
                if ticket is None:
                    continue
                try:
                    r.result = ticket.result(
                        timeout=max(0.0, give_up - time.perf_counter()))
                    r.done = time.perf_counter()
                except TimeoutError:
                    r.error = "unanswered"
                except Exception as e:
                    r.error = f"{type(e).__name__}: {e}"
            sender.join()
        w.end = max([r.done for r in reqs if not math.isnan(r.done)],
                    default=w.start)
        w.stats_after = self.service.stats()
        w.requests = reqs
        resilience.append(self._resilience())
        for phase, (a, b) in zip(("lead_in", "window"),
                                 zip(resilience, resilience[1:])):
            self.phase_notes[f"service_{phase}"] = {
                k: b[k] - a[k] for k in RESILIENCE if b[k] != a[k]}
        return w

    def latencies(self, w: Window) -> np.ndarray:
        """Client-side latency of every request scheduled in the window,
        from its due time; a failed or unanswered request ranks above
        every answer (at the window's length plus its grace)."""
        cap = (w.requests[-1].due - w.start) + float(self.traffic["grace_s"])
        return np.array([r.done - r.due if r.result is not None else cap
                         for r in w.requests])

    def attempted(self, w: Window) -> int:
        return len(w.requests)

    def notes(self, w: Window) -> dict:
        return dict(self.phase_notes)

    def close(self) -> None:
        svc, self.service = getattr(self, "service", None), None
        if svc is not None:
            svc.stop()


def make(cell, seed: int, seconds: float) -> OpenLoop:
    return OpenLoop(cell, seed, seconds)
