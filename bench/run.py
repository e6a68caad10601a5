"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. It loads the cell named in ``BENCHMARK.json``,
generates the trace from ``--seed``, warms up, measures for ``--seconds``,
checks what the window produced against the plain reference, and prints one
JSON object as the last line of standard output. With ``--trace 1`` the
metrics are the cell's per-layer ones, read from the program's spans and a
profiler trace of the window; with ``--trace 0`` its end-to-end ones.

It measures on a TPU only: with another first device, fewer chips than the
cell asks for, or a device missing from ``bench/peaks.json`` it exits 2 and
prints no result. JAX's compile cache is the checkout's ``.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python3 -m bench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # the checkout's own compile cache, whatever the machine sets: the path
    # is part of the cache key, and two checkouts must share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench.catalog import load_cell, load_peaks

    cell = load_cell(args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    try:
        peaks = load_peaks(devices[0].device_kind, ROOT)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    from repro.core.jax_solve import enable_compile_cache

    enable_compile_cache()
    from bench.harness import measure

    out = measure(cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), device=devices[0], t_start=T_START)
    share = out["device"]["memory_peak_bytes"] / float(peaks["hbm_bytes"])
    print(f"device memory peak: {share:.6f} of {peaks['hbm_bytes']:.0f} bytes "
          f"({peaks['source']})", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
