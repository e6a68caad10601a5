"""Readings that set the limits of ``correct``: the program's and the
control's numbers, over many seeds, in one process.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds 15

For each seed it makes a run's set-up and a short measured window at the
cell's own load, then compares the same sampled decisions twice: the
program's allocations, and the control, the reference's optimum held in
float32 (the precision below the configuration's float64) put in the
program's place. A benchmark run draws its work from the mix's
``base_seed`` and only permutes the tenants by its seed, so every run
solves the same instances; here each seed is also the base seed, so the
readings span as many different sets of instances as seeds. The
benchmark's own runs do not run it. Like ``bench/run.py`` it measures on a
TPU only. Prints one JSON line per seed, then the largest program reading
and the smallest control reading of each number.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

from bench.run import ROOT


def readings(cell, seeds, seconds):
    """Per seed: the program's numbers, the control's and the exact gates,
    on work drawn from that seed."""
    from bench import check, harness

    rows = []
    for seed in seeds:
        drawn = dataclasses.replace(
            cell, traffic=dict(cell.traffic, base_seed=int(seed)))
        adapter, events, _ = harness.replay(
            drawn, seed=seed, seconds=seconds, window=harness._Window(False))
        ref_trace = check.Trace(events)
        program, control = check.compare(adapter.decisions, ref_trace,
                                         cell.config, seed)
        control.pop("shape_mismatch", None)
        rows.append({"seed": seed, "base_seed": drawn.traffic["base_seed"],
                     "decisions": len(adapter.decisions),
                     "program": program, "control": control,
                     "gates": harness.gates(adapter, ref_trace, cell.config,
                                            program)})
    return rows


def summary(rows):
    names = sorted({k for r in rows for k in r["program"]})
    return {name: {"program_max": max(r["program"].get(name, 0.0) for r in rows),
                   "control_min": min(r["control"].get(name, 0.0) for r in rows)}
            for name in names}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="python3 -m bench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.catalog import load_cell

    cell = load_cell(args.workload, ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("bench.control: no TPU", file=sys.stderr)
        return 2
    from repro.core.jax_solve import enable_compile_cache

    enable_compile_cache()
    rows = readings(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"workload": args.workload, "summary": summary(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
