"""gset2000-4die.fabric on four host CPU devices (a child process, since
the device count is fixed when JAX starts): sound, it is correct and its
batches span four devices; without the exchange between chips, it is
not correct."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _run(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, os.path.join(HERE,
                                                       "fourdie_run.py"),
                          fault], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_psum", False)])
def test_exchange_between_chips(fault, correct):
    res = _run(fault)
    assert res["device"]["count"] == 4
    assert res["correct"] is correct, res["checks"]
    if not correct:
        assert res["checks"]["restarts_unlike_reference"]["value"] > 0
