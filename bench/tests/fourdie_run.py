"""Child process of ``test_bench_faults_4die``: one small
gset2000-4die.fabric run on four forced host CPU devices, optionally with
the fabric's exchange between chips left out. Prints the result line."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(fault: str) -> None:
    import pytest

    from bench.tests import faults
    from bench.tests.small import run_small
    from repro.core import device_model
    from repro.distributed import fabric

    with pytest.MonkeyPatch.context() as mp:
        init = device_model.DeviceModel.__init__
        mp.setattr(device_model.DeviceModel, "__init__",
                   lambda self, *a, compute_dtype="bfloat16", **k:
                   init(self, *a, compute_dtype=compute_dtype, **k))
        if fault == "no_psum":
            mp.setattr(fabric.FieldExchange, "_make_exchange",
                       faults.no_psum(fabric.FieldExchange._make_exchange))
            fabric.FieldExchange._FN_CACHE.clear()
        res = run_small("gset2000-4die.fabric", chips=4)
    print(json.dumps(res))


if __name__ == "__main__":
    main(sys.argv[1])
