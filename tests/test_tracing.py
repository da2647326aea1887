"""The program's spans (``repro.tracing``) in a profiler trace recorded
here on the CPU: each appears, nested in its parent, with its counts; the
service's per-request queue time and flush id; the kernels' names."""
import dataclasses
import glob
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Problem, ProblemSuite, deadline_to_budget, get_solver
from repro.problems.gset import gset_problem
from repro.serve import IsingService
from repro.tracing import phase, span


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    thread: tuple          # (plane, line index): one host thread
    counts: dict

    def inside(self, other: "Span") -> bool:
        return other.start <= self.start and self.end <= other.end


def _record(tmp, fn):
    """Run ``fn`` under the profiler; its result and the program's spans
    (every host event whose name has a ``layer.`` prefix of the program)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp), "**", "*.xplane.pb"),
                      recursive=True)
    layers = ("registry.", "machine.", "engine.", "fabric.", "serve.",
              "test.")
    spans = []
    with warnings.catch_warnings():
        # reading event stats warns about the binding's own types
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for k, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(layers):
                        spans.append(Span(ev.name, int(ev.start_ns),
                                          int(ev.end_ns), (plane.name, k),
                                          dict(ev.stats)))
    return out, spans


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _parent(span_, spans, name, same_thread=True):
    """The one ``name`` span that holds ``span_``."""
    holders = [p for p in _named(spans, name) if span_.inside(p) and (
        not same_thread or p.thread == span_.thread)]
    assert len(holders) == 1, (span_, len(holders))
    return holders[0]


# -- the module itself -------------------------------------------------------

def test_span_and_phase_write_events_with_counts(tmp_path):
    ledger = {"t_x": 0.0}

    def work():
        with span("test.outer", problems=3) as sp:
            sp.set_metadata(path="scan")
            with phase("test.phase", ledger, "t_x", sweep=1):
                time.sleep(0.002)

    _, spans = _record(tmp_path, work)
    outer, = _named(spans, "test.outer")
    inner, = _named(spans, "test.phase")
    assert outer.counts == {"problems": 3, "path": "scan"}
    assert inner.counts == {"sweep": 1} and inner.inside(outer)
    # the ledger and the span measure the same boundary
    assert 0.002 <= ledger["t_x"]
    assert abs(ledger["t_x"] - (inner.end - inner.start) * 1e-9) < 1e-3


def test_no_profiler_no_events_same_ledger():
    ledger = {"t_x": 0.0}
    for _ in range(3):
        with phase("test.phase", ledger, "t_x"):
            pass
        with span("test.outer", flush=None) as sp:
            sp.set_metadata(bytes=1)
    assert ledger["t_x"] > 0.0


# -- offline: the engine solver ----------------------------------------------

@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    suite = ProblemSuite([Problem.random_qubo(n, 0.5, seed=i)
                          for i, n in enumerate((12, 16, 40))])
    solver = get_solver("engine")
    solver.solve(suite, runs=8, seed=1, block=32)       # compile first
    return _record(tmp_path_factory.mktemp("engine"),
                   lambda: solver.solve(suite, runs=8, seed=1, block=32))


def test_engine_solve_spans_nest(engine_trace):
    rep, spans = engine_trace
    top, = _named(spans, "registry.solve")
    assert top.counts == {"problems": 3, "runs": 8}
    for name in ("registry.make_machine", "machine.lfsr_init", "engine.run",
                 "machine.wait", "machine.readback", "registry.scatter"):
        found = _named(spans, name)
        assert found, name
        for s in found:
            _parent(s, spans, "registry.solve")
    # one bucket per padded size: (12, 16) -> 32, 40 -> 64
    assert len(_named(spans, "machine.lfsr_init")) == rep.dispatches == 2
    assert len(_named(spans, "engine.run")) == 2
    for run in _named(spans, "engine.run"):
        assert run.counts["path"] in ("scan", "fused")
        assert {"block_r", "j_dtype"} <= set(run.counts)
    init, = [s for s in _named(spans, "machine.lfsr_init")
             if s.counts["problems"] == 1]
    assert init.counts["runs"] == 8
    # the LFSR states go to the device, 8 bytes per (problem, run, tile):
    # both buckets (32 and 64 spins) are one tile wide
    for init in _named(spans, "machine.lfsr_init"):
        assert init.counts["bytes"] == init.counts["problems"] * 8 * 1 * 8
    # wait, then readback, after the dispatch, each once per bucket
    for run in _named(spans, "engine.run"):
        wait = min((s for s in _named(spans, "machine.wait")
                    if s.start >= run.end), key=lambda s: s.start)
        back = min((s for s in _named(spans, "machine.readback")
                    if s.start >= wait.end), key=lambda s: s.start)
        assert back.counts["bytes"] > 0


def test_scatter_wall_is_a_duration(engine_trace):
    rep, spans = engine_trace
    top, = _named(spans, "registry.solve")
    assert 0 < rep.wall_s <= (top.end - top.start) * 1e-9


# -- the fabric --------------------------------------------------------------

SWEEPS = 2


@pytest.fixture(scope="module")
def fabric_trace(tmp_path_factory):
    p = gset_problem(130, seed=42, degree=5.0)
    s = get_solver("fabric-jax", anneal_sweeps=0.5, inner_runs=4,
                   outer_sweeps=SWEEPS)
    s.solve(p, runs=2, seed=5)                          # compile first
    return _record(tmp_path_factory.mktemp("fabric"),
                   lambda: s.solve(p, runs=2, seed=5))


def test_fabric_phases_once_per_colour_and_sweep(fabric_trace):
    rep, spans = fabric_trace
    fab = rep.meta["fabric"]
    assert fab["n_colors"] == 2
    top, = _named(spans, "registry.solve")
    for name in ("fabric.fields", "fabric.assemble", "fabric.engine",
                 "fabric.accept"):
        found = _named(spans, name)
        assert len(found) == fab["n_colors"] * SWEEPS, name
        assert all(s.inside(top) for s in found)
        assert sorted((s.counts["sweep"], s.counts["color"])
                      for s in found) == [(w, c) for w in range(SWEEPS)
                                          for c in range(2)]
        assert all(s.counts["tiles"] >= 1 for s in found)
    for run in _named(spans, "engine.run"):
        _parent(run, spans, "fabric.engine")


def test_fabric_ledger_is_fed_by_the_spans(fabric_trace):
    rep, spans = fabric_trace
    for key, name in (("t_fields", "fabric.fields"),
                      ("t_engine", "fabric.engine"),
                      ("t_accept", "fabric.accept")):
        ledger = [s[key] for s in rep.meta["fabric"]["per_sweep"]]
        traced = [sum(s.end - s.start for s in _named(spans, name)
                      if s.counts["sweep"] == w) * 1e-9
                  for w in range(SWEEPS)]
        # one boundary: the ledger's host clock and the trace's agree
        np.testing.assert_allclose(ledger, traced, rtol=0.1, atol=2e-4)


# -- the service -------------------------------------------------------------

@pytest.fixture(scope="module", params=[None, 30.0],
                ids=["no-deadline", "watchdog"])
def service_trace(request, tmp_path_factory):
    """Six requests in flushes of at most three; with a deadline the
    dispatch runs on the watchdog's own thread."""
    probs = [Problem.random_qubo(12, 0.5, seed=100 + i) for i in range(6)]
    warm = get_solver("engine")
    budget = deadline_to_budget(request.param, reference_s=1.0)
    for b in (1, 2, 3):
        warm.solve(ProblemSuite(probs[:b]), runs=8, seed=2, block=16,
                   budget=budget)

    def serve():
        with IsingService(solver="engine", runs=8, seed=2, block=16,
                          max_batch=3, max_wait_s=0.05) as svc:
            tickets = [svc.submit(p, deadline_s=request.param)
                       for p in probs]
            return [t.result(timeout=300) for t in tickets]

    results, spans = _record(tmp_path_factory.mktemp("serve"), serve)
    return request.param, results, spans


def test_service_spans_nest(service_trace):
    deadline, results, spans = service_trace
    flushes = _named(spans, "serve.flush")
    assert flushes and _named(spans, "serve.wait")
    assert all("pending" in s.counts for s in _named(spans, "serve.wait"))
    for name in ("serve.dispatch", "serve.validate", "serve.deliver"):
        found = _named(spans, name)
        assert len(found) >= len(flushes), name
        for s in found:
            # the watchdog runs the dispatch on a thread of its own
            same = not (name == "serve.dispatch" and deadline)
            parent = _parent(s, spans, "serve.flush", same_thread=same)
            assert s.counts["flush"] == parent.counts["flush"]
    for d in _named(spans, "serve.dispatch"):
        assert d.counts["attempt"] == 0 and d.counts["hedge"] == 0
        assert (d.thread != flushes[0].thread) == bool(deadline)
    for s in _named(spans, "registry.solve"):
        _parent(s, spans, "serve.dispatch")
    for f in flushes:
        assert f.counts["padded_n"] == 16 and 1 <= f.counts["size"] <= 3
    assert sum(f.counts["size"] for f in flushes) == len(results)
    for v in _named(spans, "serve.validate"):
        assert v.counts["rows"] >= 1


def test_queue_time_and_flush_id(service_trace):
    _, results, spans = service_trace
    for r in results:
        assert not r.cached
        assert 0.0 <= r.queued_s <= r.latency_s
    by_flush: dict = {}
    for r in results:
        by_flush.setdefault(r.flush, []).append(r)
    # a flush's requests share its id, and the flush's spans carry it
    for fid, members in by_flush.items():
        assert all(m.batch_size == len(members) for m in members)
    sizes = {f.counts["flush"]: f.counts["size"]
             for f in _named(spans, "serve.flush")}
    assert {fid: len(m) for fid, m in by_flush.items()} == sizes


def test_cached_answer_has_no_flush():
    p = Problem.random_qubo(12, 0.5, seed=7)
    with IsingService(solver="engine", runs=4, seed=2, block=16,
                      max_batch=1, max_wait_s=0.0) as svc:
        first = svc.submit(p).result(timeout=300)
        again = svc.submit(p).result(timeout=300)
    assert first.flush is not None and not first.cached
    assert again.cached and again.flush is None and again.queued_s == 0.0


# -- stable kernel names -----------------------------------------------------

def _pallas_names(jaxpr) -> list:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None:
                names += _pallas_names(getattr(inner, "jaxpr", inner))
    return names


@pytest.mark.parametrize("j_dtype,kernel", [
    ("float32", "fused_anneal_kernel"), ("bfloat16", "fused_anneal_kernel"),
    ("int8", "fused_anneal_kernel_int8"),
    # two problems of at most 64 spins share a tile
    ("float32", "fused_anneal_kernel_x2"),
    ("bfloat16", "fused_anneal_kernel_x2"),
    ("int8", "fused_anneal_kernel_int8_x2")])
def test_kernels_carry_their_own_names(j_dtype, kernel):
    from repro.core.device_model import DeviceModel
    from repro.core.perturbation import DEFAULT_PERTURBATION, NOMINAL
    from repro.kernels.ising_anneal import fused_anneal_kernel
    from repro.kernels.sb_kernel import fused_sb_kernel
    # int8 runs only under the unit schedule: no perturbation, no leakage
    unit = j_dtype == "int8"
    dev = DeviceModel(n_spins=8)
    if unit:
        dev = dataclasses.replace(dev, tau_leak_sweeps=float("inf"))
    z = jnp.zeros((1, 8, 8), jnp.float32)
    zp = jnp.zeros((2 if kernel.endswith("_x2") else 1, 8, 8), jnp.float32)
    anneal = jax.make_jaxpr(lambda J, v: fused_anneal_kernel(
        J, v, dev=dev, pert=NOMINAL if unit else DEFAULT_PERTURBATION,
        block_r=8, j_dtype=j_dtype))(zp, zp)
    sb = jax.make_jaxpr(lambda J, x, y: fused_sb_kernel(
        J, x, y, n_steps=4, block_r=8))(z, z, z)
    assert _pallas_names(anneal.jaxpr) == [kernel]
    assert _pallas_names(sb.jaxpr) == ["sb_anneal_kernel"]


@pytest.mark.parametrize("path,problems,dies", [
    ("fused", 2, 2), ("fused", 1, 1), ("scan", 2, 1)])
def test_engine_run_carries_dies_per_tile(tmp_path, path, problems, dies):
    """``engine.run`` records how many dies each kernel tile anneals: two
    small problems share one on the fused path; the scan path has none."""
    from repro.core.device_model import DeviceModel
    from repro.core.engine import AnnealEngine
    from repro.core.lfsr import lfsr_voltage_inits
    dev = DeviceModel(n_spins=16, anneal_sweeps=0.25)
    eng = AnnealEngine(device=dev, path=path)
    J = jnp.asarray(np.stack([Problem.random_qubo(16, 0.5, seed=i).levels
                              for i in range(problems)]), jnp.float32)
    v0 = jnp.asarray(np.stack([lfsr_voltage_inits(16, 8, seed=i)
                               for i in range(problems)]))
    eng.run(J, v0).v_final.block_until_ready()          # compile first
    _, spans = _record(tmp_path, lambda: eng.run(J, v0))
    run, = _named(spans, "engine.run")
    assert run.counts["path"] == path
    assert run.counts["dies_per_tile"] == dies
