"""Lazy progress accounting, checked against the eager walk it replaced.

The scheduler used to walk every running job before each popped event; it
now settles a job only where something reads or changes its progress. Only
the evaluation order of the same arithmetic changed, so finish times,
decisions and delivered work must match the walk's to floating-point
rounding. ``lazy_progress_golden.json`` holds what the walking scheduler
produced on three seeded traces. Between them they cover a migration stall,
a host failure that slows a running job, a tenant leaving with jobs still
running, an ``until`` that cuts jobs mid-run, and ``oef-coop``,
``oef-noncoop`` and a baseline policy.

Regenerate the file with the scheduler under comparison on the path:

    PYTHONPATH=<checkout>/src python tests/test_lazy_progress.py OUT.json
"""
import dataclasses
import json
import math
import os
import shutil
import sys

import pytest

from repro.core.types import ClusterSpec
from repro.service import Event, EventKind, OnlineScheduler, synthetic_trace
from repro.service.faults import ChaosEngine, FaultPlan, standard_plan
from repro.service.journal import Journal, resume_scheduler

CLUSTER = ClusterSpec.paper_cluster()
GOLDEN = os.path.join(os.path.dirname(__file__), "lazy_progress_golden.json")


def _noncoop_chaos():
    base = synthetic_trace(8, cluster=CLUSTER, duration_s=3600.0,
                           host_failures_per_hour=2.0, seed=11)
    return ChaosEngine(standard_plan(seed=5), CLUSTER).chaos_trace(base)


def _coop_leave():
    events = synthetic_trace(5, cluster=CLUSTER, duration_s=2400.0, seed=4)
    events += [
        Event(600.0, EventKind.HOST_FAIL, payload={"type": 2, "host": 0}),
        Event(900.0, EventKind.TENANT_LEAVE, tenant="tenant1"),
        Event(1300.0, EventKind.TENANT_LEAVE, tenant="tenant3"),
        Event(1500.0, EventKind.HOST_RECOVER, payload={"type": 2, "host": 0}),
    ]
    return sorted(events, key=lambda e: e.time)


def _gavel_churn():
    events = synthetic_trace(6, cluster=CLUSTER, duration_s=3600.0,
                             host_failures_per_hour=3.0, seed=8)
    events.append(Event(1700.0, EventKind.TENANT_LEAVE, tenant="tenant2"))
    return sorted(events, key=lambda e: e.time)


#: name -> (policy, function making the trace, until)
CASES = {
    "noncoop-chaos-until": ("oef-noncoop", _noncoop_chaos, 2700.0),
    "coop-leave-fail": ("oef-coop", _coop_leave, None),
    "gavel-churn-until": ("gavel", _gavel_churn, 3000.0),
}


def _run(name, sched_hook=None):
    policy, build, until = CASES[name]
    sched = OnlineScheduler(CLUSTER, policy)
    if sched_hook is not None:
        sched_hook(sched)
    report = sched.run(build(), until=until)
    return sched, report


def observe(sched, report):
    """What the comparison reads: per-job finish time and done work at the
    horizon, per-tenant delivered work, each decision's record, and the
    queue pops of the whole run (settling adds or removes none)."""
    return {
        "finish_time": {j.job_id: j.finish_time for j in sched.jobs.values()},
        "done": {j.job_id: j.done for j in sched.jobs.values()},
        "delivered": dict(report.tenant_delivered_work),
        "solves": [[r.time, r.n_tenants, r.reused]
                   for r in sched.metrics.solves],
        "events_popped": sched.events_popped,
    }


def _watch(sched, seen):
    """Note which accounting paths the replay takes, from outside."""
    handle, resolve = sched._handle, sched._resolve

    def watched_handle(ev, queue):
        rates = {j.job_id: j.rate for j in sched.jobs.values()}
        if ev.kind is EventKind.TENANT_LEAVE and any(
                j.tenant == ev.tenant and j.rate > 0 for j in sched.jobs.values()):
            seen.add("leave_with_running_jobs")
        handle(ev, queue)
        if ev.kind is EventKind.HOST_FAIL and any(
                0 < j.rate < rates[j.job_id] for j in sched.jobs.values()):
            seen.add("host_fail_slows_a_running_job")

    def watched_resolve(now, queue):
        resolve(now, queue)
        if any(j.rate > 0 and j.resume_at > now for j in sched.jobs.values()):
            seen.add("migration_stall")

    sched._handle, sched._resolve = watched_handle, watched_resolve


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CASES))
def test_lazy_settling_matches_the_eager_walk(name, golden):
    sched, report = _run(name)
    got, want = observe(sched, report), golden[name]
    finished = {k for k, v in got["finish_time"].items() if v is not None}
    assert finished == {k for k, v in want["finish_time"].items() if v is not None}
    assert set(got["done"]) == set(want["done"])
    for job_id in finished:
        assert got["finish_time"][job_id] == pytest.approx(
            want["finish_time"][job_id], rel=1e-9)
    for job_id, done in want["done"].items():
        assert got["done"][job_id] == pytest.approx(done, rel=1e-9), job_id
    assert got["delivered"].keys() == want["delivered"].keys()
    for tenant, work in want["delivered"].items():
        assert got["delivered"][tenant] == pytest.approx(work, rel=1e-9), tenant
    assert len(got["solves"]) == len(want["solves"])
    for (t, n, reused), (t0, n0, reused0) in zip(got["solves"], want["solves"]):
        assert (n, reused) == (n0, reused0)
        assert t == pytest.approx(t0, rel=1e-9)
    # a finish time one ulp off may pop before or after a decision, so only
    # the run's total of pops is exact, not each record's
    assert got["events_popped"] == want["events_popped"]
    assert math.fsum(got["delivered"].values()) == pytest.approx(
        math.fsum(got["done"].values()), rel=1e-12)


def test_the_golden_traces_cover_every_settle_site():
    seen = set()
    cut_mid_run = False
    for name in CASES:
        sched, _report = _run(name, lambda s: _watch(s, seen))
        if CASES[name][2] is not None:
            cut_mid_run |= any(not j.finished and j.done > 0
                               for j in sched.jobs.values())
    assert seen == {"leave_with_running_jobs", "host_fail_slows_a_running_job",
                    "migration_stall"}
    assert cut_mid_run
    assert {"oef-coop", "oef-noncoop"} <= {p for p, _b, _u in CASES.values()}


# ---------------------------------------------------------------------------
# journal: snapshots written before jobs carried a settle anchor
# ---------------------------------------------------------------------------


def _chaos_trace():
    base = synthetic_trace(6, cluster=CLUSTER, duration_s=3600.0,
                           host_failures_per_hour=2.0, seed=3)
    plan = FaultPlan(seed=7, storms=3, storm_size=3, corrupt_profiles=3,
                     solver_faults=())
    return ChaosEngine(plan, CLUSTER).chaos_trace(base)


def _journaled_run(trace, jdir, until=None):
    journal = Journal(jdir, snapshot_every=10)
    try:
        return OnlineScheduler(CLUSTER, "oef-noncoop").run(
            list(trace), until=until, journal=journal)
    finally:
        journal.close()


def _owed(state):
    """Running jobs of a snapshot whose credit stops short of
    ``last_advance``."""
    t = state["last_advance"]
    jobs = {j["job_id"]: j for j in state["jobs"]}
    return [jobs[i] for i in state["running_jobs"] if jobs[i]["rate"] > 0
            and max(jobs[i]["settled_at"], jobs[i]["resume_at"]) < t]


def _drop_anchors(state):
    """Rewrite a snapshot into the form the walking scheduler wrote: every
    running job credited up to ``last_advance``, and no ``settled_at``."""
    t = state["last_advance"]
    for job in _owed(state):
        start = max(job["settled_at"], job["resume_at"])
        credited = min(job["total_work"] - job["done"], job["rate"] * (t - start))
        if credited > 0:
            job["done"] += credited
            state["metrics"]["delivered"][job["tenant"]] += credited
    for job in state["jobs"]:
        del job["settled_at"]


def _close(a, b, path="report"):
    if isinstance(a, dict):
        assert a.keys() == b.keys(), path
        for k in a:
            _close(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _close(x, y, f"{path}[{i}]")
    elif isinstance(a, float) and not isinstance(b, bool):
        assert a == pytest.approx(b, rel=1e-9, nan_ok=True), path
    else:
        assert a == b, path


def test_snapshot_without_settle_anchors_resumes_to_the_same_report(tmp_path):
    trace = _chaos_trace()
    ref = _journaled_run(trace, str(tmp_path / "ref"))
    crash_dir = str(tmp_path / "crash")
    times = sorted(e.time for e in trace)
    _journaled_run(trace, crash_dir, until=times[len(times) // 2])
    journal = Journal(crash_dir, snapshot_every=10)
    # resume from the latest snapshot in which the anchor matters: some
    # running job is owed progress since its last settle
    for snap in reversed(journal.available_snapshots()):
        path = os.path.join(journal._snap_dir(snap), "state.json")
        with open(path) as f:
            state = json.load(f)
        if _owed(state):
            break
        shutil.rmtree(journal._snap_dir(snap))
    assert snap > 0 and _owed(state)
    _drop_anchors(state)
    with open(path, "w") as f:
        json.dump(state, f)
    resumed = resume_scheduler(crash_dir, list(trace), snapshot_every=10)
    views = []
    for rep in (ref, resumed):
        d = dataclasses.asdict(rep)
        d.pop("resolve_latency_ms_mean")
        d.pop("resolve_latency_ms_p95")
        views.append(d)
    _close(views[1], views[0])


if __name__ == "__main__":
    out = {}
    for case in sorted(CASES):
        out[case] = observe(*_run(case))
    with open(sys.argv[1], "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
