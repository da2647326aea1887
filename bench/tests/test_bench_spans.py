"""The readers of the program's spans and counters: in a traced small run
of each cell they read a finite value of at least 0; on a program that
writes no such span or counter they read nothing and do not raise."""
import json
import math
import os
import subprocess
import sys
import types

import pytest

from bench.lib import manifest
from bench.tests.small import run_small
from bench.trace import Reduced

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

NEW = {
    "chip64.batch": ("registry.lfsr_ms", "registry.readback_ms"),
    "gset2000.fabric": ("fabric.dispatch_ms",),
    "gset2000-4die.fabric": ("fabric.dispatch_ms",),
    "chip64.stream": ("serve.queue_ms", "serve.validate_ms"),
}

FOUR_HOST_DEVICES = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests.small import run_small
res = run_small("gset2000-4die.fabric", trace=True, chips=4)
print(json.dumps(res["metrics"]))
"""


def _metrics(cell: str) -> dict:
    if cell != "gset2000-4die.fabric":
        return run_small(cell, trace=True)["metrics"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_HOST_DEVICES.format(root=ROOT, src=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_run_reads_the_program_spans(chip_precision, cell):
    metrics = _metrics(cell)
    listed = {m["name"] for m in manifest.load()["per_layer"]
              if manifest.reports(m, cell)}
    assert set(NEW[cell]) <= listed
    for name in NEW[cell]:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
        assert metrics[name]["unit"] == "ms"


def test_readers_read_nothing_without_the_program_spans():
    bare = Reduced(lo=0, hi=10_000, devices=[[(0, 5_000, "fusion")]],
                   calls=[(0, 10_000)],
                   host=[(0, 10_000, "bench.call: no finer host event"),
                         (100, 200, "np.asarray(jax.Array)")])
    answer = types.SimpleNamespace(cached=False, latency_s=0.01)
    window = types.SimpleNamespace(
        requests=[types.SimpleNamespace(result=answer)], calls=[])
    ctx = types.SimpleNamespace(trace=bare, window=window, notes={})
    for name in sorted({n for names in NEW.values() for n in names}):
        assert manifest.code("metrics", name).read(ctx) is None, name
