"""Benchmark aggregator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; a module failure prints a FAILED
row and flips the exit code but the rest still run. Running the
``service_throughput`` module (directly or through here) regenerates
``BENCH_service.json`` at the repo root — the artifact CI and docs track
for solver-latency regressions. Figure map: docs/benchmarks.md. Usage:
    PYTHONPATH=src python -m benchmarks.run [--only fig9]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "table1_properties",
    "fig4_strategyproofness",
    "fig5a_sharing_incentive",
    "fig5b_multi_jobtype",
    "fig6_envy_freeness",
    "fig7_throughput_noncoop",
    "fig8_throughput_coop",
    "fig9_jct",
    "straggler_ablation",
    "fig10a_scalability",
    "fig10b_sensitivity",
    "extensions",
    "service_throughput",
    "chaos_recovery",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()
    mods = [m for m in MODULES if args.only is None or args.only in m]
    print("name,us_per_call,derived")
    failures = 0
    t0 = time.perf_counter()
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}", flush=True)
        except Exception as e:
            failures += 1
            print(f"{name},nan,FAILED {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(limit=3, file=sys.stderr)
    print(f"# total_seconds={time.perf_counter()-t0:.1f} failures={failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
