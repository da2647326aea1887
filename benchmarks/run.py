"""Benchmark orchestrator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig4_success]
    PYTHONPATH=src python -m benchmarks.run --quick   # solver-matrix smoke

Prints ``name,us_per_call,derived`` CSV per benchmark; JSON artifacts land
in experiments/bench/. ``--quick`` runs only the registry solver-matrix
smoke (every registered solver on one shared suite), writing
``BENCH_solvers.json`` at the repo root for CI to archive.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.utils import enable_compile_cache

from . import (device_robustness, fig4_success, fig4_trajectories,
               fig5_sr_density, fig5_tts, kernel_throughput, roofline_bench,
               serve_chaos, serve_fleet, serve_throughput, solver_matrix,
               table2_ets, workloads)

ALL = {
    "fig4_trajectories": fig4_trajectories.run,
    "fig4_success": fig4_success.run,
    "fig5_sr_density": fig5_sr_density.run,
    "fig5_tts": fig5_tts.run,
    "table2_ets": table2_ets.run,
    "kernel_throughput": kernel_throughput.run,
    "roofline_bench": roofline_bench.run,
    "solver_matrix": solver_matrix.run,
    "serve_throughput": serve_throughput.run,
    "serve_chaos": serve_chaos.run,
    "serve_fleet": serve_fleet.run,
    "device_robustness": device_robustness.run,
    "workloads": workloads.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale problem counts (hours on CPU)")
    ap.add_argument("--quick", action="store_true",
                    help="solver-matrix smoke only (CI job)")
    ap.add_argument("--only", nargs="*", choices=list(ALL))
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only or (["solver_matrix"] if args.quick else list(ALL))
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        try:
            ALL[name](full=args.full)
        except Exception as e:
            traceback.print_exc()
            failures.append((name, e))
    if failures:
        print(f"{len(failures)} benchmark(s) FAILED: "
              f"{[n for n, _ in failures]}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
