"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--slow] [--only NAME]

Emits ``name,us_per_call,derived`` CSV lines per the harness contract.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

MODULES = [
    "table1_workload",      # Table 1
    "table2_platforms",     # Table 2
    "fig34_latency_model",  # Figs 3-4
    "fig56_accuracy_model", # Figs 5-6
    "fig7_synthetic_allocation",  # Fig 7 (+ Table 3)
    "fig810_practical_allocation",  # Figs 8 & 10
    "fig9_pareto",          # Fig 9
    "allocation_bench",     # canonical 16x4 instance -> BENCH_allocation.json
    "kernel_bench",         # Pallas MC kernels
    "roofline_report",      # §Roofline (from dry-run artifacts)
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slow", action="store_true",
                    help="full-size sweeps (paper-scale)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    failures = 0
    for name in MODULES:
        if args.only and args.only != name:
            continue
        print(f"# === {name} ===", flush=True)
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.time()
        try:
            mod.main(fast=not args.slow)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{name}.FAILED,0.0,", flush=True)
        print(f"# --- {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
