"""Online-service benchmark: scheduler decisions/sec and re-solve latency vs
cluster size.

Replays seeded synthetic traces through the event-driven
``repro.service.OnlineScheduler`` on two ladders:

  - the LP ladder (4/8/16 tenants, ``oef-coop``) — the cooperative solve with
    its O(n^2) envy constraints, tracking the historical scaling wall;
  - the jax ladder (128/512/1024 tenants, ``oef-noncoop`` with
    ``backend="jax"``) — the batched jitted water-filling tier of
    ``repro.core.jax_solve``, prewarmed so jit compiles stay out of the
    measured re-solve latency;
  - the coop-jax ladder (64/128/256 tenants, ``oef-coop`` with
    ``backend="jax"``) — the deduplicating primal–dual tier of
    ``repro.core.jax_coop``; its ``BENCH_service.json`` keys carry a
    ``_coopjax`` suffix so they never collide with the non-coop jax ladder.
    The bar: the 256-tenant p95 stays below the LP ladder's 16-tenant figure.

Reported per scale: decision throughput (solves/sec of wall time, with
events/sec context) and re-solve latency mean/p95 plus the incremental-reuse
hit rate. The acceptance bar for the jax tier is p95 re-solve latency at
1024 tenants at or below the LP ladder's 16-tenant figure (~5.4 ms).

Also dumps the raw numbers to ``BENCH_service.json`` at the repo root so CI
and the docs can track regressions.
"""
from __future__ import annotations

import gc
import json
import os
import time

from repro.core.types import ClusterSpec
from repro.service import OnlineScheduler, synthetic_trace
from repro.service.traces import default_job_types

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_service.json")

SCALES = (
    # (n_tenants, devices-per-type multiplier)
    (4, 1),
    (8, 2),
    (16, 4),
)

#: jax-backend ladder: large tenant counts, non-cooperative policy (the
#: cooperative LP's envy constraints are quadratic in tenants and would
#: dominate wall time long before these scales).
JAX_SCALES = (
    (128, 16),
    (512, 64),
    (1024, 128),
)

#: coop-jax ladder: the cooperative program on the primal–dual tier. The
#: trace draws tenants from the paper's six-profile job-type catalog, so the
#: reduced instance stays small after dedup regardless of tenant count.
COOP_JAX_SCALES = (
    (64, 8),
    (128, 16),
    (256, 32),
)


#: (arrival horizon s, mean per-tenant interarrival s) of both jax ladders.
JAX_TRACE = (1800.0, 1200.0)


def rung(n_tenants: int, scale: int, *, duration_s: float,
         mean_interarrival_s: float):
    """Fleet and seeded trace of one ladder rung: the paper's three GPU
    types with ``8 * scale`` devices each."""
    cluster = ClusterSpec(types=("rtx3070", "rtx3080", "rtx3090"),
                          m=(8 * scale, 8 * scale, 8 * scale))
    events = synthetic_trace(
        n_tenants, job_types=default_job_types("paper"), cluster=cluster,
        duration_s=duration_s, mean_interarrival_s=mean_interarrival_s,
        mean_work_s=1200.0, seed=0)
    return cluster, events


def _replay(n_tenants: int, scale: int, policy: str, backend: str,
            *, duration_s: float, mean_interarrival_s: float):
    cluster, events = rung(n_tenants, scale, duration_s=duration_s,
                           mean_interarrival_s=mean_interarrival_s)
    sched = OnlineScheduler(cluster, policy, min_resolve_interval_s=30.0,
                            solver_backend=backend)
    # Latency-benchmark hygiene: move everything allocated so far (trace,
    # jax programs, module state) out of the cyclic GC's working set so a
    # gen-2 collection landing inside a timed re-solve doesn't show up as
    # solver tail latency.
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    report = sched.run(events, until=7200.0)
    wall = time.perf_counter() - t0
    return report, wall


def run() -> list:
    rows = []
    dump = {}

    ladders = [(SCALES, "oef-coop", "numpy", 3600.0, 300.0, "")]
    try:
        from repro.core import jax_coop, jax_solve
    except ImportError:  # jax not installed: LP ladder only
        jax_solve = jax_coop = None
    if jax_solve is not None:
        jax_solve.enable_compile_cache()
        ladders.append((JAX_SCALES, "oef-noncoop", "jax", *JAX_TRACE, ""))
        ladders.append((COOP_JAX_SCALES, "oef-coop", "jax", *JAX_TRACE,
                        "_coopjax"))

    k = len(default_job_types("paper")[0].speedup)
    for scales, policy, backend, duration_s, interarrival_s, suffix in ladders:
        if backend == "jax":
            # compile every padding bucket up front; compiles are a one-time
            # cost and must not pollute the p95 re-solve latency
            if policy == "oef-coop":
                # the PD tier solves the deduplicated instance: its buckets
                # are group counts, bounded by the job-type catalog size
                jax_coop.prewarm(len(default_job_types("paper")), k)
            else:
                jax_solve.prewarm(max(n for n, _ in scales), k)
        for n_tenants, scale in scales:
            report, wall = _replay(
                n_tenants, scale, policy, backend,
                duration_s=duration_s, mean_interarrival_s=interarrival_s)
            solves_per_s = report.n_solves / max(wall, 1e-9)
            events_per_s = report.n_events / max(wall, 1e-9)
            tag = f"n{n_tenants}_m{8 * scale}x3{suffix}"
            rows.append((f"service/decide_{tag}", wall / max(report.n_solves, 1) * 1e6,
                         f"{solves_per_s:.0f} solves/s {events_per_s:.0f} ev/s"))
            rows.append((f"service/resolve_{tag}", report.resolve_latency_ms_mean * 1e3,
                         f"p95={report.resolve_latency_ms_p95:.2f}ms "
                         f"reused={report.n_reused_solves}/{report.n_solves} "
                         f"backend={backend}"))
            dump[tag] = {
                "n_tenants": n_tenants,
                "devices": 24 * scale,
                "policy": policy,
                "backend": backend,
                "wall_s": wall,
                "n_events": report.n_events,
                "n_solves": report.n_solves,
                "n_reused_solves": report.n_reused_solves,
                "solves_per_sec": solves_per_s,
                "events_per_sec": events_per_s,
                "resolve_latency_ms_mean": report.resolve_latency_ms_mean,
                "resolve_latency_ms_p95": report.resolve_latency_ms_p95,
                "jobs_finished": report.jobs_finished,
                "fallback_count": report.fallback_count,
                "solver_backends": report.solver_backends,
            }
    with open(BENCH_PATH, "w") as f:
        json.dump(dump, f, indent=2, sort_keys=True)
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
