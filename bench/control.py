"""The control: a cell run with the reference put in the program's place,
one precision below the one the configuration states.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <n> ...]
        [--seconds <s>]

The program's anneal (``AnnealEngine.run``, under the registry, the
fabric and the service alike) is replaced by ``reference.anneal`` at the
lower precision; everything else of the run is the cell's own, the check
included. The control must come out not correct: it shows that the
comparison can fail. The benchmark's own runs never run it. Prints the
compared numbers as one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CONTROL_PRECISION = "float8_e4m3fn"


def install(precision: str = CONTROL_PRECISION):
    """Swap the program's anneal for the reference at ``precision``;
    returns a function that restores it."""
    import jax.numpy as jnp

    from bench.reference import anneal as ref
    from repro.core import engine
    from repro.core.annealer import AnnealResult

    original = engine.AnnealEngine.run

    def run(self, J, v0, key=None, record_every=0):
        J = jnp.asarray(J, jnp.float32)
        v, sig = ref.anneal(J, jnp.asarray(v0, jnp.float32),
                            ref.schedule(J.shape[-1]), precision=precision)
        s = sig.astype(jnp.float32)
        e = -0.5 * jnp.einsum("pri,pij,prj->pr", s, J, s,
                              precision="highest")
        return AnnealResult(v_final=v, sigma=s, energy=e)

    engine.AnnealEngine.run = run
    return lambda: setattr(engine.AnnealEngine, "run", original)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from bench import run as harness
    restore = install()
    try:
        for seed in args.seed:
            res = harness.execute(args.workload, seed, args.seconds, False)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "precision": CONTROL_PRECISION,
                              "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)
    finally:
        restore()
    return 0


if __name__ == "__main__":
    sys.exit(main())
