"""Smoke run of the online scheduler's device path on one TPU chip.

    python chip_smoke.py

One process, through the library's own entry points, in four phases that
each print a line:

1. device  - the first JAX device must be a TPU; anything else exits 1.
2. parity  - seeded Monge instances (n=1024, k=3) through the
             non-cooperative jax tier against the numpy water-filling, and
             catalog-drawn n=256 instances through the cooperative
             primal-dual tier against the scipy LP, at the tolerances of
             tests/test_jax_solve.py and tests/test_jax_coop.py.
3. noncoop - ``oef-noncoop`` replay, 1024 tenants on 3 x 1024 devices (the
             top rung of ``benchmarks/service_throughput.JAX_SCALES``).
4. coop    - ``oef-coop`` replay, 256 tenants on 3 x 256 devices (the top
             rung of ``COOP_JAX_SCALES``).

The scheduler is built as ``python -m repro.service --backend jax`` builds
it, guardrails on. A rung fails unless every solve that was not reused ran on
the jax tier, with no LP fallback, no degraded solve, no last-known-good
floor, no compile inside the replay, at least ``MIN_SOLVES`` solves that
were not reused and at least one finished job. The last line of stdout is one JSON object naming
the device. Compiled programs are cached (see
``repro.core.jax_solve.enable_compile_cache``), so a second run in the same
checkout prewarms faster.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

import jax  # noqa: E402
import numpy as np  # noqa: E402

#: tests/test_jax_solve.py PARITY_TOL (absolute, on tau and every X entry).
NONCOOP_TOL = 1e-9
#: tests/test_jax_coop.py TOL (relative objective gap, absolute envy).
COOP_TOL = 1e-6
#: fewest solves that were not reused a rung must make inside ``until``.
MIN_SOLVES = 20
#: simulated seconds each rung replays: the trace's arrival horizon.
UNTIL_S = 1800.0


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _emit(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields, sort_keys=True)}", flush=True)


def check_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (first JAX device is "
                 f"{dev.platform!r}); this check runs on the chip only")
    return dev


def monge_instance(rng, n: int, k: int):
    """tests/test_jax_solve.py's construction: W[l, j] = a_l ** c_j with
    both exponents ascending."""
    a = np.cumsum(rng.uniform(0.05, 0.8, size=n)) + 1.0
    c = np.cumsum(rng.uniform(0.05, 0.6, size=k))
    W = np.power(a[:, None], (c - c[0])[None, :])
    return W, rng.integers(1, 9, size=k).astype(float)


def catalog_instance(rng, n: int, g: int = 5, k: int = 3):
    """tests/test_jax_coop.py's construction: n tenants drawn from a
    g-profile catalog."""
    cat = np.cumprod(1.0 + rng.uniform(0.05, 1.0, size=(g, k)), axis=1)
    cat /= cat[:, :1]
    W = cat[rng.integers(0, g, size=n)]
    return W, rng.uniform(1.0, 4.0, size=k) * n / 4


def check_parity(n_noncoop: int = 1024, n_coop: int = 256,
                 seeds: int = 3) -> None:
    from repro.core import jax_coop, jax_solve, oef

    rng = np.random.default_rng(0)
    tau_err = x_err = 0.0
    for _ in range(seeds):
        W, m = monge_instance(rng, n_noncoop, 3)
        ref = oef.solve_noncoop_waterfill(W, m)
        tau, X = jax_solve.solve_noncoop_fast_jax(W, m)
        tau_err = max(tau_err, abs(tau - ref.meta["tau"]))
        x_err = max(x_err, float(np.abs(X - ref.X).max()))
    _emit("parity oef-noncoop", n=n_noncoop, k=3, instances=seeds,
          max_tau_err=tau_err, max_x_err=x_err, tol=NONCOOP_TOL)
    _require(tau_err <= NONCOOP_TOL and x_err <= NONCOOP_TOL,
             "non-cooperative jax tier disagrees with the numpy water-filling")

    obj_err = envy = over = 0.0
    for _ in range(seeds):
        W, m = catalog_instance(rng, n_coop)
        lp = oef.solve_coop(W, m)
        got = jax_coop.solve_coop_pd(W, m)
        o_pd, o_lp = float((W * got.X).sum()), float((W * lp.X).sum())
        obj_err = max(obj_err, abs(o_pd - o_lp) / max(abs(o_lp), 1.0))
        own = np.einsum("lk,lk->l", W, got.X)
        E = W @ got.X.T - own[:, None]
        np.fill_diagonal(E, 0.0)
        envy = max(envy, float(E.max()))
        over = max(over, float((got.X.sum(axis=0) - m).max() / m.max()))
    _emit("parity oef-coop", n=n_coop, k=3, instances=seeds,
          max_rel_obj_err=obj_err, max_envy=envy, max_rel_overcommit=over,
          tol=COOP_TOL)
    _require(obj_err <= COOP_TOL and envy <= COOP_TOL and over <= 1e-9,
             "cooperative primal-dual tier disagrees with the scipy LP")


def replay_rung(policy: str, n_tenants: int, scale: int,
                until: float = UNTIL_S, min_solves: int = MIN_SOLVES) -> None:
    from benchmarks.service_throughput import JAX_TRACE, rung
    from repro.core import jax_coop, jax_solve
    from repro.service.__main__ import build_parser, make_scheduler
    from repro.service.traces import default_job_types

    duration_s, interarrival_s = JAX_TRACE
    cluster, events = rung(n_tenants, scale, duration_s=duration_s,
                           mean_interarrival_s=interarrival_s)
    args = build_parser().parse_args(["--policy", policy, "--backend", "jax"])
    sched = make_scheduler(args, cluster)
    k = len(cluster.types)
    if policy == "oef-coop":
        # the PD tier solves the deduplicated instance: its buckets are
        # group counts, bounded by the job-type catalog size
        tier, n_max = jax_coop._pd_segment, len(default_job_types("paper"))
        prewarm = jax_coop.prewarm
    else:
        tier, n_max = jax_solve._solve_padded, n_tenants
        prewarm = jax_solve.prewarm
    t0 = time.perf_counter()
    buckets = prewarm(n_max, k)
    prewarm_s = time.perf_counter() - t0
    programs = tier._cache_size()
    t0 = time.perf_counter()
    report = sched.run(events, until=until)
    wall_s = time.perf_counter() - t0
    solved = [s for s in sched.metrics.solves if not s.reused]
    lat_ms = np.asarray([s.latency_s * 1e3 for s in solved])
    off_tier = sorted({s.backend for s in solved} - {"jax"})
    compiles = tier._cache_size() - programs
    _emit(f"rung {policy}", tenants=n_tenants, devices=list(cluster.m),
          until_s=until, prewarm_s=prewarm_s, prewarm_buckets=buckets,
          events=report.n_events, solves=report.n_solves,
          reused=report.n_reused_solves,
          solve_ms_p50=float(np.percentile(lat_ms, 50)) if lat_ms.size else None,
          solve_ms_p95=float(np.percentile(lat_ms, 95)) if lat_ms.size else None,
          wall_s=wall_s, solver_backends=report.solver_backends,
          fallback_count=report.fallback_count,
          degraded_solves=report.degraded_solves, anomalies=report.anomalies,
          jobs_finished=report.jobs_finished, compiles_in_replay=compiles)
    _require(len(solved) >= min_solves,
             f"{policy}: {len(solved)} solves not reused < {min_solves}")
    _require(solved and not off_tier,
             f"{policy}: solves ran off the jax tier: {off_tier}")
    _require(report.fallback_count == 0, f"{policy}: LP fallbacks")
    _require(report.degraded_solves == 0, f"{policy}: degraded solves")
    _require("solver_floor" not in report.anomalies,
             f"{policy}: last-known-good floor engaged")
    _require(report.jobs_finished > 0, f"{policy}: no job finished")
    _require(compiles == 0, f"{policy}: {compiles} compiles inside the replay")


def main() -> int:
    dev = check_device()
    from repro.core.jax_solve import enable_compile_cache

    _emit("device", platform=dev.platform, kind=dev.device_kind,
          count=len(jax.devices()), jax=jax.__version__,
          compile_cache=enable_compile_cache())
    check_parity()
    from benchmarks.service_throughput import COOP_JAX_SCALES, JAX_SCALES

    replay_rung("oef-noncoop", *JAX_SCALES[-1])
    replay_rung("oef-coop", *COOP_JAX_SCALES[-1])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
