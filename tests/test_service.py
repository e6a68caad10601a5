"""Online service subsystem: event-queue determinism, trace replay
round-trip, re-solve throttle/warm-start behavior, host-failure handling,
and the service-vs-round-simulator steady-state agreement check."""
import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.core.profiler import paper_job_type
from repro.core.simulator import SimJob, SimTenant
from repro.core.types import ClusterSpec, JobTypeProfile
from repro.service import (
    Event,
    EventKind,
    EventQueue,
    OnlineScheduler,
    read_trace_csv,
    synthetic_trace,
    write_trace_csv,
)
from repro.service.journal import Journal, recover_scheduler
from repro.service.scheduler import SERVICE_POLICIES, crossval_static
from repro.service.traces import default_cluster, default_job_types

CLUSTER = ClusterSpec.paper_cluster()


def _deterministic_view(report):
    """Report minus wall-clock solver-latency telemetry (all that may vary
    between two replays of the same trace)."""
    d = dataclasses.asdict(report)
    d.pop("resolve_latency_ms_mean")
    d.pop("resolve_latency_ms_p95")
    return d


def _static_tenants(n=3, seed=0, total_work=1e9, jobs=6, fleet="paper"):
    rng = np.random.default_rng(seed)
    if fleet == "paper":
        catalog = [paper_job_type(name)
                   for name in ("vgg", "lstm", "resnet", "transformer")]
    else:
        catalog = default_job_types(fleet)
    tenants = []
    for i in range(n):
        jt = catalog[i % len(catalog)]
        tenants.append(SimTenant(
            name=f"tenant{i}", job_types={jt.name: jt},
            jobs=[SimJob(job_id=f"t{i}-j{q}", tenant=f"tenant{i}", job_type=jt.name,
                         workers=int(rng.choice([1, 1, 2, 4])), total_work=total_work)
                  for q in range(jobs)]))
    return tenants


# ---------------------------------------------------------------------------
# event queue
# ---------------------------------------------------------------------------


def test_event_queue_same_time_pops_in_push_order():
    q = EventQueue()
    evs = [Event(5.0, EventKind.JOB_SUBMIT, tenant="a", job_id=f"j{i}") for i in range(8)]
    for ev in evs:
        q.push(ev)
    q.push(Event(1.0, EventKind.TENANT_JOIN, tenant="a"))
    out = list(q.drain())
    assert out[0].kind == EventKind.TENANT_JOIN
    assert [e.job_id for e in out[1:]] == [f"j{i}" for i in range(8)]


def test_synthetic_trace_deterministic_under_seed():
    kw = dict(duration_s=3600.0, host_failures_per_hour=1.0,
              cluster=CLUSTER, seed=7)
    a = synthetic_trace(4, **kw)
    b = synthetic_trace(4, **kw)
    assert a == b
    c = synthetic_trace(4, **{**kw, "seed": 8})
    assert a != c


@pytest.mark.parametrize("fleet", ["paper", "tpu"])
@pytest.mark.parametrize("policy", SERVICE_POLICIES)
def test_service_replay_deterministic(policy, fleet):
    cluster = default_cluster(fleet)
    events = synthetic_trace(3, job_types=default_job_types(fleet),
                             duration_s=2400.0, seed=3)
    reports = []
    for _ in range(2):
        sched = OnlineScheduler(cluster, policy)
        reports.append(sched.run(events))
    assert _deterministic_view(reports[0]) == _deterministic_view(reports[1])


# ---------------------------------------------------------------------------
# trace CSV round-trip
# ---------------------------------------------------------------------------


def test_trace_csv_roundtrip_identical_events_and_schedule(tmp_path):
    events = synthetic_trace(3, duration_s=2400.0, seed=11,
                             host_failures_per_hour=0.5, cluster=CLUSTER)
    path = str(tmp_path / "trace.csv")
    write_trace_csv(events, path)
    replayed = read_trace_csv(path)
    assert replayed == events  # bit-exact payloads (repr floats + JSON)
    r1 = OnlineScheduler(CLUSTER, "oef-coop").run(events)
    r2 = OnlineScheduler(CLUSTER, "oef-coop").run(replayed)
    assert _deterministic_view(r1) == _deterministic_view(r2)


def test_trace_csv_rejects_internal_kinds(tmp_path):
    with pytest.raises(ValueError):
        write_trace_csv([Event(0.0, EventKind.RESOLVE)], str(tmp_path / "t.csv"))


# ---------------------------------------------------------------------------
# service-vs-simulator steady state (acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fleet", ["paper", "tpu"])
@pytest.mark.parametrize("policy", SERVICE_POLICIES)
def test_service_matches_simulator_steady_state(policy, fleet):
    """On a static workload the online service must converge to the round
    simulator's per-tenant throughputs within 1%."""
    r = crossval_static(_static_tenants(3, fleet=fleet), default_cluster(fleet),
                        policy, rounds=5)
    assert r["max_rel_err"] < 0.01, r


def test_crossval_weighted_multi_jobtype():
    """Weighted tenants with multiple job types use the virtual-user path in
    both engines and must still agree."""
    jts = {n: paper_job_type(n) for n in ("vgg", "lstm")}
    tenants = [
        SimTenant(name="a", job_types=dict(jts), weight=2.0,
                  jobs=[SimJob("a-j0", "a", "vgg", 2, 1e9)]),
        SimTenant(name="b", job_types={"resnet": paper_job_type("resnet")},
                  jobs=[SimJob("b-j0", "b", "resnet", 2, 1e9)]),
    ]
    r = crossval_static(tenants, CLUSTER, "oef-coop", rounds=4)
    assert r["max_rel_err"] < 0.01, r


# ---------------------------------------------------------------------------
# throttle, warm start, dirty batching
# ---------------------------------------------------------------------------


def test_resolve_throttle_batches_arrival_storm():
    """100 submits in one minute with a 60 s throttle => solves stay bounded
    (first solve + throttled batches), not one per event."""
    jt = paper_job_type("vgg")
    events = [Event(0.0, EventKind.TENANT_JOIN, tenant="t0", payload={
        "weight": 1.0,
        "job_types": [{"name": jt.name, "speedup": list(jt.speedup), "min_demand": 1}]})]
    for i in range(100):
        events.append(Event(0.5 + i * 0.5, EventKind.JOB_SUBMIT, tenant="t0",
                            job_id=f"j{i}", payload={"job_type": jt.name, "workers": 1,
                                                     "total_work": 1e8}))
    sched = OnlineScheduler(CLUSTER, "oef-coop", min_resolve_interval_s=60.0)
    report = sched.run(events, until=240.0)
    assert report.n_events >= 101
    assert report.n_solves <= 6, report.n_solves
    storm_solves = [s for s in sched.metrics.solves if s.dirty_events > 1]
    assert storm_solves, "expected at least one batched dirty set"


def test_warm_start_reuse_on_job_finish():
    """A job finishing does not change (W, m): the next solve must reuse the
    previous allocation via the incremental hook."""
    jt = paper_job_type("vgg")
    events = [Event(0.0, EventKind.TENANT_JOIN, tenant="t0", payload={
        "weight": 1.0,
        "job_types": [{"name": jt.name, "speedup": list(jt.speedup), "min_demand": 1}]})]
    for i in range(3):
        events.append(Event(0.0, EventKind.JOB_SUBMIT, tenant="t0", job_id=f"j{i}",
                            payload={"job_type": jt.name, "workers": 1,
                                     "total_work": 600.0 * (i + 1)}))
    sched = OnlineScheduler(CLUSTER, "oef-coop", min_resolve_interval_s=1.0)
    report = sched.run(events)
    assert report.jobs_finished == 3
    assert report.n_reused_solves >= 1


# ---------------------------------------------------------------------------
# continuous-time correctness
# ---------------------------------------------------------------------------


def test_single_job_jct_analytic():
    """One tenant, one 2-worker job on an otherwise empty cluster: rate =
    2 workers x speedup of the granted type; JCT = work / rate."""
    jt = JobTypeProfile("uniform", (1.0, 1.0, 1.0))
    events = [
        Event(0.0, EventKind.TENANT_JOIN, tenant="t0", payload={
            "weight": 1.0,
            "job_types": [{"name": "uniform", "speedup": [1.0, 1.0, 1.0],
                           "min_demand": 1}]}),
        Event(0.0, EventKind.JOB_SUBMIT, tenant="t0", job_id="j0",
              payload={"job_type": "uniform", "workers": 2, "total_work": 100.0}),
    ]
    sched = OnlineScheduler(CLUSTER, "oef-coop")
    report = sched.run(events)
    assert report.jobs_finished == 1
    # 2 workers, speedup 1.0 on every type, single host => rate 2/s => JCT 50s
    assert report.mean_jct_s == pytest.approx(50.0, rel=1e-6)
    assert report.mean_queue_delay_s == pytest.approx(0.0, abs=1e-9)


def test_host_failure_drops_capacity_and_recovers():
    jt = paper_job_type("vgg")
    payload = {"weight": 1.0, "job_types": [
        {"name": jt.name, "speedup": list(jt.speedup), "min_demand": 1}]}
    events = [
        Event(0.0, EventKind.TENANT_JOIN, tenant="t0", payload=dict(payload)),
        Event(0.0, EventKind.JOB_SUBMIT, tenant="t0", job_id="j0",
              payload={"job_type": jt.name, "workers": 4, "total_work": 1e9}),
        Event(100.0, EventKind.HOST_FAIL, payload={"type": 2, "host": 0}),
        Event(100.0, EventKind.HOST_FAIL, payload={"type": 2, "host": 1}),
        Event(500.0, EventKind.HOST_RECOVER, payload={"type": 2, "host": 0}),
        Event(500.0, EventKind.HOST_RECOVER, payload={"type": 2, "host": 1}),
    ]
    sched = OnlineScheduler(CLUSTER, "oef-coop", min_resolve_interval_s=1.0)
    sched.run(events, until=1000.0)
    # after the failures the solver saw a 3070/3080-only cluster
    caps = [tuple(s.time for s in sched.metrics.solves)]
    assert sched.metrics.solves, caps
    est_during_outage = [s for s in sched.metrics.solves if 100.0 <= s.time < 500.0]
    assert est_during_outage, "expected a re-solve during the outage"
    # and the job kept running end-to-end (no crash, work delivered)
    assert sched.metrics.delivered["t0"] > 0


def test_tenant_leave_frees_capacity():
    jt = paper_job_type("vgg")
    payload = {"weight": 1.0, "job_types": [
        {"name": jt.name, "speedup": list(jt.speedup), "min_demand": 1}]}
    events = []
    for t in ("t0", "t1"):
        events.append(Event(0.0, EventKind.TENANT_JOIN, tenant=t, payload=dict(payload)))
        events.append(Event(0.0, EventKind.JOB_SUBMIT, tenant=t, job_id=f"{t}-j0",
                            payload={"job_type": jt.name, "workers": 1,
                                     "total_work": 1e9}))
    events.append(Event(300.0, EventKind.TENANT_LEAVE, tenant="t1"))
    sched = OnlineScheduler(CLUSTER, "oef-noncoop", min_resolve_interval_s=1.0)
    sched.run(events, until=900.0)
    # t1 gone: the last estimate covers only t0, at full-cluster throughput
    assert set(sched.last_estimate) == {"t0"}


def test_profile_update_triggers_resolve():
    jt = paper_job_type("vgg")
    events = [
        Event(0.0, EventKind.TENANT_JOIN, tenant="t0", payload={
            "weight": 1.0, "job_types": [
                {"name": jt.name, "speedup": list(jt.speedup), "min_demand": 1}]}),
        Event(0.0, EventKind.JOB_SUBMIT, tenant="t0", job_id="j0",
              payload={"job_type": jt.name, "workers": 1, "total_work": 1e9}),
        Event(200.0, EventKind.PROFILE_UPDATE, tenant="t0",
              payload={"job_type": jt.name, "speedup": [1.0, 2.0, 4.0]}),
    ]
    sched = OnlineScheduler(CLUSTER, "oef-coop", min_resolve_interval_s=1.0)
    sched.run(events, until=600.0)
    # new speedup vector in effect: estimate reflects the 4x top type
    assert sched.last_estimate["t0"] > 8.0  # 8 devices of rtx3090 x ~weight


def test_migration_stall_not_refunded_by_resolve():
    """Regression: a re-solve during a migration stall that keeps the same
    assignment must not pull resume_at back to `now` (refunding the
    checkpoint/restart overhead)."""
    jt = JobTypeProfile("uniform", (1.0, 1.0, 1.0))
    payload = {"weight": 1.0, "job_types": [
        {"name": "uniform", "speedup": [1.0, 1.0, 1.0], "min_demand": 1}]}
    events = [
        Event(0.0, EventKind.TENANT_JOIN, tenant="t0", payload=dict(payload)),
        Event(0.0, EventKind.JOB_SUBMIT, tenant="t0", job_id="j0",
              payload={"job_type": "uniform", "workers": 4, "total_work": 1e9}),
        # kill the host j0 runs on: forces a migration (30s stall)
        Event(100.0, EventKind.HOST_FAIL, payload={"type": 2, "host": 0}),
        # unrelated dirty event 5s into the stall: re-solve keeps assignment
        Event(105.0, EventKind.JOB_SUBMIT, tenant="t0", job_id="j1",
              payload={"job_type": "uniform", "workers": 1, "total_work": 1e9}),
    ]
    sched = OnlineScheduler(CLUSTER, "oef-coop", min_resolve_interval_s=1.0,
                            migration_overhead_s=30.0)
    sched.run(events, until=200.0)
    j0 = sched.jobs["j0"]
    # j0 migrated off the failed host at t=100 => stall until 130; the t=105
    # re-solve (same assignment) must not have pulled it back to 105
    assert j0.resume_at == pytest.approx(130.0), j0.resume_at


def test_resolve_timer_no_float_livelock():
    """Regression: the RESOLVE timer used to be scheduled at
    ``last_solve + interval`` and compared via ``now - last >= interval``;
    when the sum rounded down the comparison stayed false and the timer
    re-armed itself at the same timestamp forever. This trace (tenants=4,
    duration=1200, seed=9) hit that live-lock — the run must drain."""
    events = synthetic_trace(4, duration_s=1200.0, seed=9)
    report = OnlineScheduler(CLUSTER, "oef-noncoop").run(events)
    assert report.jobs_unfinished == 0
    assert report.n_solves < 10 * report.n_events


def test_tpu_cluster_kind_profiles():
    jts = default_job_types("tpu")
    cluster = default_cluster("tpu")
    assert all(len(j.speedup) == cluster.k for j in jts)
    events = synthetic_trace(2, job_types=jts, duration_s=1200.0, seed=5)
    report = OnlineScheduler(cluster, "oef-noncoop").run(events)
    assert report.n_solves > 0


# ---------------------------------------------------------------------------
# work counters: SolveRecord.events_popped / jobs_advanced
# ---------------------------------------------------------------------------


def _counted_run(monkeypatch, events):
    """Replay ``events`` with every queue pop and every settle of a running
    job counted outside the scheduler; returns the scheduler, the totals and
    the totals at each solve record."""
    sched = OnlineScheduler(CLUSTER, "oef-noncoop")
    seen = {"pops": 0, "settled": 0}
    at_record = []
    pop, settle, on_solve = EventQueue.pop, sched._settle, sched.metrics.on_solve

    def counting_pop(queue):
        seen["pops"] += 1
        return pop(queue)

    def counting_settle(job, t):
        if job.rate > 0:  # a running job brought forward to t
            seen["settled"] += 1
        settle(job, t)

    def marking_on_solve(rec):
        at_record.append((seen["pops"], seen["settled"]))
        on_solve(rec)

    monkeypatch.setattr(EventQueue, "pop", counting_pop)
    monkeypatch.setattr(sched, "_settle", counting_settle)
    monkeypatch.setattr(sched.metrics, "on_solve", marking_on_solve)
    sched.run(events)
    return sched, seen, at_record


def test_solve_records_count_every_pop_and_walked_job(monkeypatch):
    """``jobs_advanced`` counts settles of running jobs: the bulk settle of
    each decision, single finishing jobs, and the final settle at the
    horizon, which falls after the last record."""
    events = synthetic_trace(4, duration_s=2400.0, seed=3, cluster=CLUSTER)
    sched, seen, at_record = _counted_run(monkeypatch, events)
    recs = sched.metrics.solves
    assert len(recs) == len(at_record) > 1
    marks = [(0, 0)] + at_record
    assert [(r.events_popped, r.jobs_advanced) for r in recs] == [
        (b[0] - a[0], b[1] - a[1]) for a, b in zip(marks, marks[1:])]
    pops_after_last = seen["pops"] - at_record[-1][0]
    assert sum(r.events_popped for r in recs) + pops_after_last \
        == seen["pops"] == sched.events_popped
    assert sched.jobs_advanced == seen["settled"] > 0
    # predicted finishes and re-solve timers are popped as well
    assert seen["pops"] > len(events)
    # settling is lazy: fewer settles than pops, where a walk per pop
    # visited every running job
    assert seen["settled"] < seen["pops"]


def _work_counts(sched):
    return ([(r.events_popped, r.jobs_advanced) for r in sched.metrics.solves],
            sched.events_popped, sched.jobs_advanced)


def test_work_counters_do_not_depend_on_tracing():
    events = synthetic_trace(4, duration_s=2400.0, seed=3, cluster=CLUSTER)
    plain = OnlineScheduler(CLUSTER, "oef-noncoop")
    plain.run(events)
    tracer = obs.Tracer()
    obs.set_tracer(tracer)
    try:
        traced = OnlineScheduler(CLUSTER, "oef-noncoop")
        traced.run(events)
    finally:
        obs.set_tracer(None)
    assert any(s[0] == "advance" for s in tracer.spans)
    assert _work_counts(plain) == _work_counts(traced)


def test_search_iters_sum_exactly_over_a_window(monkeypatch):
    """``SolveRecord.search_iters`` is each decision's device iterations of
    the general price search, counted outside the scheduler at the search
    segment: the records between any two decisions sum to exactly the
    iterations run between them, reused decisions and water-filling count
    0, and tracing changes nothing."""
    from repro.core import jax_general
    from repro.core.profiler import ProfilingAgent
    from repro.core.types import TPU_FLEET
    from repro.service.traces import TPU_WORKLOADS

    agent = ProfilingAgent(TPU_FLEET, error_pct=0.05, seed=8)
    jts = [agent.profile(c) for c in TPU_WORKLOADS for _ in range(3)]
    tpu = default_cluster("tpu")
    events = synthetic_trace(12, job_types=jts, cluster=tpu,
                             duration_s=2400.0, seed=8)
    ran = {"iters": 0}
    segment = jax_general._search_segment

    def counting_segment(*args):
        ran["iters"] += jax_general.SEG_ITERS
        return segment(*args)

    monkeypatch.setattr(jax_general, "_search_segment", counting_segment)
    runs = []
    for traced in (False, True):
        tracer = obs.Tracer() if traced else None
        obs.set_tracer(tracer)
        try:
            sched = OnlineScheduler(tpu, "oef-noncoop", solver_backend="jax")
            at_record = []
            on_solve = sched.metrics.on_solve
            monkeypatch.setattr(sched.metrics, "on_solve", lambda rec: (
                at_record.append(ran["iters"]), on_solve(rec)))
            start = ran["iters"]
            sched.run(events)
        finally:
            obs.set_tracer(None)
        recs = sched.metrics.solves
        marks = [start] + at_record
        assert [r.search_iters for r in recs] == [
            b - a for a, b in zip(marks, marks[1:])]
        assert all(r.search_iters == 0 for r in recs if r.reused)
        lo, hi = 1, len(recs) - 1
        assert sum(r.search_iters for r in recs[lo:hi]) \
            == marks[hi] - marks[lo] > 0
        runs.append([r.search_iters for r in recs])
    assert runs[0] == runs[1]
    # water-filling decisions run no search
    paper = OnlineScheduler(CLUSTER, "oef-noncoop", solver_backend="jax")
    paper.run(synthetic_trace(4, duration_s=2400.0, seed=3, cluster=CLUSTER))
    assert paper.metrics.solves and all(
        r.search_iters == 0 for r in paper.metrics.solves)


def test_resumed_run_keeps_the_work_counters(tmp_path):
    events = synthetic_trace(4, duration_s=2400.0, seed=3, cluster=CLUSTER)
    mid = sorted(e.time for e in events)[len(events) // 2]
    runs = {}
    for name, until in (("ref", None), ("crash", mid)):
        journal = Journal(str(tmp_path / name), snapshot_every=5)
        runs[name] = OnlineScheduler(CLUSTER, "oef-noncoop")
        try:
            runs[name].run(list(events), until=until, journal=journal)
        finally:
            journal.close()
    sched, journal, n_applied = recover_scheduler(str(tmp_path / "crash"),
                                                  snapshot_every=5)
    try:
        sched.run(journal.events(journal.n_applied) + list(events)[n_applied:],
                  journal=journal)
    finally:
        journal.close()
    assert sched.metrics.solves and _work_counts(sched) == _work_counts(runs["ref"])


def test_resumed_run_keeps_the_price_search_warm_start(tmp_path):
    """Off the staircase class the next decision starts from the previous
    answer's prices; a snapshot carries them, so a resumed run searches as
    long and answers as the uninterrupted one."""
    from repro.core.profiler import ProfilingAgent
    from repro.core.types import TPU_FLEET
    from repro.service.traces import TPU_WORKLOADS

    agent = ProfilingAgent(TPU_FLEET, error_pct=0.05, seed=9)
    jts = [agent.profile(c) for c in TPU_WORKLOADS for _ in range(3)]
    tpu = default_cluster("tpu")
    events = synthetic_trace(12, job_types=jts, cluster=tpu, duration_s=2400.0,
                             mean_work_s=300.0, seed=9)
    mid = sorted(e.time for e in events)[len(events) // 2]
    runs = {}
    for name, until in (("ref", None), ("crash", mid)):
        journal = Journal(str(tmp_path / name), snapshot_every=5)
        runs[name] = OnlineScheduler(tpu, "oef-noncoop", solver_backend="jax")
        try:
            runs[name].run(list(events), until=until, journal=journal)
        finally:
            journal.close()
    sched, journal, n_applied = recover_scheduler(str(tmp_path / "crash"),
                                                  snapshot_every=5)
    try:
        sched.run(journal.events(journal.n_applied) + list(events)[n_applied:],
                  journal=journal)
    finally:
        journal.close()
    ref = runs["ref"].metrics.solves
    assert sum(r.search_iters for r in ref) > 0
    assert [r.search_iters for r in sched.metrics.solves] == [
        r.search_iters for r in ref]
    np.testing.assert_array_equal(sched._prev_alloc.X, runs["ref"]._prev_alloc.X)
