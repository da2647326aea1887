"""The control of a cell without landscape perturbation: the run with the
unit-schedule reference put in the program's place, its voltages held one
precision below the stated one.

    python3 bench/control_unit.py --workload <cell> --seed <n> [--seed <n> ...]
        [--seconds <s>]

``bench/control.py`` lowers the precision of the anneal's operand. Under a
unit schedule that proves nothing: the operand is +-1 times integer levels,
exact at any width, float8 included. The accumulator is no better a choice:
bfloat16 holds integers up to 256 exactly and the field sums of a density
0.5 die are mostly well below that, so it would seldom differ. What the
stated arithmetic does round is the float32 voltage state, ``v + sum * dd``
on every step. So the program's anneal (``AnnealEngine.run``) is replaced
by ``reference.anneal_unit`` with its voltages rounded to bfloat16 after
every step (``v0`` at 0.25 and 0.75 is exact there, so only the steps
differ); everything else of the run is the cell's own, the check included.
It must come out not correct on every seed; with the voltages at float32,
the stated arithmetic, it must come out correct. The benchmark's own runs
never run it. Prints the compared numbers as one JSON line per seed.
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

CONTROL_STATE = "bfloat16"


def install(state: str = CONTROL_STATE):
    """Swap the program's anneal for the unit-schedule reference with its
    voltages held at ``state``; returns a function that restores it."""
    import jax.numpy as jnp

    from bench.reference import anneal_unit as ref
    from repro.core import engine
    from repro.core.annealer import AnnealResult

    original = engine.AnnealEngine.run

    def run(self, J, v0, key=None, record_every=0):
        J = jnp.asarray(J, jnp.float32)
        v, sig = ref.anneal(J, jnp.asarray(v0, jnp.float32),
                            n_steps=self.device.n_steps, state=state)
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
                              "state": CONTROL_STATE,
                              "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)
    finally:
        restore()
    return 0


if __name__ == "__main__":
    sys.exit(main())
