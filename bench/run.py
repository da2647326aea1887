"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it loads, makes every input from ``--seed``, warms every
shape the cell uses (that is ``setup_s``), runs the measured window, reads
the device's peak memory, frees the program's state, checks the answers
against the plain reference, and prints one JSON line last on stdout.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a shorter profiled window gives its per-layer metrics.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for. Everything is found by name (``bench/lib/manifest.py``):
the cell in BENCHMARK.json, ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json``, and the code those files name under
``bench/families``, ``bench/loops``, ``bench/work``, ``bench/checks``,
``bench/metrics``; and ``bench/peaks.json``. JAX's persistent compilation
cache is the checkout's ``.jax_compile_cache``, so only the first run of
a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import manifest  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_compile_cache")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def _peaks(kind: str, require_tpu: bool):
    with open(os.path.join(manifest.BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        if require_tpu:
            raise KeyError(f"device kind {kind!r} has no entry in "
                           f"bench/peaks.json")
        return None
    return table[kind]


class Context:
    """What a metric reader or a check can read about one run."""

    def __init__(self, **kw):
        self.notes: dict = {}
        self.__dict__.update(kw)


class _CompileCounter:
    """Programs made ready (compiled, or loaded from the persistent cache)
    while counting, from JAX's own monitoring event around each: the time
    and the program's name of each."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax._src.monitoring as mon
        self._mon = mon
        self.on = False
        self.events: list = []
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, _secs, fun_name="?", **_):
        if self.on and name == self.EVENT:
            self.events.append((time.perf_counter(), fun_name))

    def split(self, at: float) -> tuple:
        """(events before ``at``, events from ``at`` on)."""
        return ([e for e in self.events if e[0] < at],
                [e for e in self.events if e[0] >= at])

    def close(self):
        self._mon.unregister_event_duration_listener(self._duration)


def _memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, AttributeError):
            pass
    return max(peaks, default=0)


def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, cell=None) -> dict:
    """One run; returns the result object the last stdout line carries.
    ``require_tpu=False`` and an explicit ``cell`` are for the tests,
    which drive a run on the CPU at a small size."""
    cell = cell if cell is not None else manifest.cell(cell_name)
    devs = _devices(cell.chips, require_tpu)
    dev = devs[0]
    peaks = _peaks(dev.device_kind, require_tpu)
    used = devs[:cell.chips]

    import jax
    if require_tpu:
        # every program of the cell in the checkout's fixed cache, so only
        # the first run of a cell there compiles; the program's own entry
        # points take the directory from the environment
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench.lib import drive
    state = tempfile.mkdtemp(prefix="bench_")
    saved = {k: os.environ.get(k) for k in ("REPRO_AUTOTUNE_CACHE",
                                            "REPRO_ORACLE_CACHE")}
    # the program's own caches start empty in every run: a tuned plan
    # left by another run would change what is measured
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(state, "tune.json")
    os.environ["REPRO_ORACLE_CACHE"] = os.path.join(state, "oracle.json")
    counter = _CompileCounter()
    drv = drive.driver(cell, seed, seconds)
    try:
        drv.setup()
        # the inputs made at set-up live through the window: keep the
        # collector from walking them again and again inside it
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T_START
        reduced = None
        if trace:
            from bench import trace as tr
            window_s = min(seconds, float(cell.traffic["trace_seconds"]))
            tdir = os.path.join(state, "trace")
            tr.start(tdir)
            counter.on = True
            try:
                w = drv.window(window_s, traced=True)
            finally:
                counter.on = False
                tr.stop()
            reduced = tr.reduce(tr.find(tdir), used)
        else:
            counter.on = True
            w = drv.window(seconds)
            counter.on = False
        memory_peak = _memory_peak(used)
        phases = {"start": setup_s - sum(drv.setup_phases.values()),
                  **drv.setup_phases, "lead_in": w.lead_in_s}
        # a lead-in of uncounted traffic is warm-up: it belongs to set-up
        setup_s += w.lead_in_s
        before, inside = counter.split(w.start)
        ctx = Context(cell=cell, drv=drv, window=w, setup_s=setup_s,
                      trace=reduced, compiles=len(inside), peaks=peaks)
        ctx.notes["setup_phases_s"] = phases
        ctx.notes["programs_made_ready"] = {
            "lead_in": len(before), "window": len(inside),
            "names": sorted({name for _, name in before + inside})[:10]}
        if hasattr(drv, "notes"):
            ctx.notes.update(drv.notes(w))
        metrics = {}
        for m in (cell.per_layer if trace else cell.end_to_end):
            value = manifest.code("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        drv.close()
        verdict = manifest.code("checks", cell.config["check"]).check(
            cell, drv, w, seed)
    finally:
        gc.unfreeze()
        counter.close()
        drv.close()
        shutil.rmtree(state, ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": verdict.correct, "attempted": verdict.attempted,
              "failed": verdict.failed, "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        result["breakdown"] = reduced.breakdown()
    result["notes"] = ctx.notes
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in verdict.checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        ok = "ok  " if c["value"] <= c["limit"] else "FAIL"
        print(f"check {ok} {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
