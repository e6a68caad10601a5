"""Chaos harness + crash-safe control plane tests.

Covers the three robustness layers end to end:
  - dispatch guardrails (transient retry, failsafe escalation, time budget,
    degraded stamping) on synthetic one-off backends;
  - scheduler guardrails (profile quarantine cycle, anomaly guards,
    last-known-good floor) driven through ordinary event traces;
  - the seeded chaos engine (deterministic merged traces, solver-fault
    injection, zero unhandled exceptions under the standard storm);
  - the journal (write-ahead + snapshots, kill-at-midpoint bit-exact
    resume, divergence detection) and the exactly-once work accounting
    across a host outage mid-job.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

from repro.core import backends
from repro.core.backends import (
    BackendError,
    add_dispatch_hook,
    dispatch,
    register_backend,
    remove_dispatch_hook,
    unregister_backend,
)
from repro.core.properties import audited_solver
from repro.core.types import Allocation, ClusterSpec
from repro.service.events import Event, EventKind
from repro.service.faults import ChaosEngine, FaultPlan, standard_plan
from repro.service.journal import Journal, recover_scheduler, resume_scheduler
from repro.service.scheduler import SERVICE_POLICIES, OnlineScheduler
from repro.service.traces import (
    default_cluster,
    default_job_types,
    synthetic_trace,
    validate_host_pairing,
)

W2 = np.array([[1.0, 2.0], [1.0, 4.0]])
M2 = np.array([4.0, 4.0])


def _equal_share(W, m) -> Allocation:
    W = np.asarray(W, dtype=np.float64)
    m = np.asarray(m, dtype=np.float64)
    n = W.shape[0]
    X = np.tile(m / n, (n, 1))
    return Allocation(X=X, rows=tuple(f"u{i}" for i in range(n)),
                      W=W, m=m, meta={})


def _view(rep):
    """Report as a dict minus the two wall-clock latency fields; compare with
    repr() because NaN != NaN under ==."""
    d = dataclasses.asdict(rep)
    d.pop("resolve_latency_ms_mean")
    d.pop("resolve_latency_ms_p95")
    return repr(d)


# ---------------------------------------------------------------------------
# dispatch guardrails
# ---------------------------------------------------------------------------


def test_transient_retry_recovers_without_degrading():
    calls = {"n": 0}

    @audited_solver
    def solve_flaky(W, m):
        calls["n"] += 1
        if calls["n"] < 3:
            raise BackendError("numerical blip", transient=True)
        return _equal_share(W, m)

    register_backend("test-flaky", "flaky", solve_flaky, default=True)
    try:
        alloc = dispatch("test-flaky", W2, M2, max_retries=2)
        assert alloc.meta["backend"] == "flaky"
        assert alloc.meta["retries"] == 2
        assert "degraded" not in alloc.meta  # retry succeeded: not a guardrail event
        assert calls["n"] == 3
    finally:
        unregister_backend("test-flaky", "flaky")


def test_exhausted_transient_retries_fall_through_degraded():
    @audited_solver
    def solve_always_transient(W, m):
        raise BackendError("never converges", transient=True)

    @audited_solver
    def solve_solid(W, m):
        return _equal_share(W, m)

    register_backend("test-exh", "solid", solve_solid)
    register_backend("test-exh", "shaky", solve_always_transient,
                     fallback="solid", default=True)
    try:
        alloc = dispatch("test-exh", W2, M2, max_retries=1)
        assert alloc.meta["backend"] == "solid"
        assert alloc.meta["fallback_from"] == "shaky"
        assert alloc.meta["degraded"] is True
    finally:
        unregister_backend("test-exh", "shaky")
        unregister_backend("test-exh", "solid")


def test_failsafe_converts_crash_into_decline():
    @audited_solver
    def solve_crashy(W, m):
        raise RuntimeError("segfault-adjacent")

    @audited_solver
    def solve_solid(W, m):
        return _equal_share(W, m)

    register_backend("test-crash", "solid", solve_solid)
    register_backend("test-crash", "crashy", solve_crashy,
                     fallback="solid", default=True)
    try:
        with pytest.raises(RuntimeError):
            dispatch("test-crash", W2, M2)  # failsafe off: crash propagates
        alloc = dispatch("test-crash", W2, M2, failsafe=True)
        assert alloc.meta["backend"] == "solid"
        assert alloc.meta["degraded"] is True
        assert "RuntimeError" in alloc.meta["fallback_reason"]
    finally:
        unregister_backend("test-crash", "crashy")
        unregister_backend("test-crash", "solid")


def test_time_budget_escalates_to_fallback():
    import time

    @audited_solver
    def solve_slow(W, m):
        time.sleep(0.05)  # repro: noqa[D104] — deliberately slow test double
        return _equal_share(W, m)

    @audited_solver
    def solve_solid(W, m):
        return _equal_share(W, m)

    register_backend("test-slow", "solid", solve_solid)
    register_backend("test-slow", "slow", solve_slow,
                     fallback="solid", default=True)
    try:
        # budget sits between the two tiers' latencies: slow blows it, the
        # fallback answers inside it
        alloc = dispatch("test-slow", W2, M2, time_budget_s=0.01)
        assert alloc.meta["backend"] == "solid"
        assert alloc.meta["degraded"] is True
    finally:
        unregister_backend("test-slow", "slow")
        unregister_backend("test-slow", "solid")

    # a slow backend with no fallback chain: the SolveTimeout surfaces
    register_backend("test-slow-nofb", "slow", solve_slow, default=True)
    try:
        with pytest.raises(BackendError, match="declined"):
            dispatch("test-slow-nofb", W2, M2, time_budget_s=0.01)
    finally:
        unregister_backend("test-slow-nofb", "slow")


def test_dispatch_hook_fault_makes_attempt_decline():
    @audited_solver
    def solve_solid(W, m):
        return _equal_share(W, m)

    register_backend("test-hook", "solid", solve_solid, default=True)
    seen = []

    def hook(program, backend, W, m):
        seen.append((program, backend))

    add_dispatch_hook(hook)
    try:
        dispatch("test-hook", W2, M2)
        assert seen == [("test-hook", "solid")]
    finally:
        remove_dispatch_hook(hook)
        unregister_backend("test-hook", "solid")


# ---------------------------------------------------------------------------
# scheduler guardrails
# ---------------------------------------------------------------------------

_CLUSTER2 = ClusterSpec(types=("a", "b"), m=(8, 8))


def _join(t, name, speedup, jt="train"):
    return Event(t, EventKind.TENANT_JOIN, tenant=name, payload={
        "job_types": [{"name": jt, "speedup": list(speedup)}]})


def _submit(t, name, job_id, work=1e5, workers=2, jt="train"):
    return Event(t, EventKind.JOB_SUBMIT, tenant=name, job_id=job_id,
                 payload={"job_type": jt, "workers": workers,
                          "total_work": work})


def _profile(t, name, speedup, jt="train"):
    return Event(t, EventKind.PROFILE_UPDATE, tenant=name,
                 payload={"job_type": jt, "speedup": list(speedup)})


def test_quarantine_cycle_nan_profile():
    trace = [
        _join(0.0, "good", (1.0, 2.0)), _submit(0.0, "good", "g0"),
        _join(0.0, "sick", (1.0, 3.0)), _submit(0.0, "sick", "s0"),
        _profile(100.0, "sick", (float("nan"), 3.0)),
        _profile(400.0, "sick", (1.0, 3.0)),
    ]
    sched = OnlineScheduler(_CLUSTER2, "oef-coop", min_resolve_interval_s=1.0)
    rep = sched.run(trace, until=800.0)
    acts = [(e["tenant"], e["action"]) for e in rep.quarantine_events]
    assert acts == [("sick", "quarantine"), ("sick", "release")]
    assert "non-finite" in rep.quarantine_events[0]["reason"]
    assert not sched.quarantined
    # while quarantined the solve saw one tenant; after release, two again
    assert any(s.quarantined == 1 for s in sched.metrics.solves)
    assert sched.metrics.solves[-1].quarantined == 0
    assert set(sched.last_estimate) == {"good", "sick"}


def test_quarantine_wrong_length_and_nonpositive():
    trace = [
        _join(0.0, "t0", (1.0, 2.0)), _submit(0.0, "t0", "j0"),
        _profile(50.0, "t0", (1.0,)),            # stale: wrong length
        _profile(200.0, "t0", (1.0, -2.0)),      # still bad: negative
        _profile(300.0, "t0", (1.0, 2.0)),       # repaired
    ]
    sched = OnlineScheduler(_CLUSTER2, "oef-noncoop", min_resolve_interval_s=1.0)
    rep = sched.run(trace, until=600.0)
    acts = [e["action"] for e in rep.quarantine_events]
    assert acts == ["quarantine", "release"]  # stays quarantined across both bad updates
    assert "entries" in rep.quarantine_events[0]["reason"]


def test_guardrails_off_means_no_quarantine():
    trace = [
        _join(0.0, "t0", (1.0, 2.0)), _submit(0.0, "t0", "j0"),
        _profile(50.0, "t0", (1.0,)),  # wrong length for a k=2 cluster
    ]
    sched = OnlineScheduler(_CLUSTER2, "oef-noncoop",
                            min_resolve_interval_s=1.0, guardrails=False)
    with pytest.raises(Exception):
        # a wrong-length speedup poisons the solver-input build and, with
        # guardrails off, the failure propagates out of the event loop
        sched.run(trace, until=400.0)


def test_anomaly_guards_count_and_ignore():
    trace = [
        _join(0.0, "t0", (1.0, 2.0)), _submit(0.0, "t0", "j0"),
        Event(10.0, EventKind.HOST_FAIL, payload={"type": 0, "host": 0}),
        Event(20.0, EventKind.HOST_FAIL, payload={"type": 0, "host": 0}),
        Event(30.0, EventKind.HOST_RECOVER, payload={"type": 0, "host": 1}),
        Event(40.0, EventKind.HOST_FAIL, payload={"type": 7, "host": 0}),
        Event(50.0, EventKind.HOST_RECOVER, payload={"type": 0, "host": 0}),
    ]
    sched = OnlineScheduler(_CLUSTER2, "oef-noncoop", min_resolve_interval_s=1.0)
    rep = sched.run(trace, until=300.0)
    assert rep.anomalies == {"duplicate_host_fail": 1,
                             "spurious_host_recover": 1,
                             "unknown_host": 1}
    assert not sched.down_hosts  # the one real outage recovered


def test_solver_floor_when_every_backend_declines():
    def total_outage(program, backend, W, m):
        raise BackendError("chaos: cluster-wide solver outage")

    trace = [
        _join(0.0, "t0", (1.0, 2.0)), _submit(0.0, "t0", "j0", work=500.0),
        _join(0.0, "t1", (1.0, 3.0)), _submit(0.0, "t1", "j1", work=500.0),
    ]
    sched = OnlineScheduler(_CLUSTER2, "oef-noncoop", min_resolve_interval_s=1.0)
    add_dispatch_hook(total_outage)
    try:
        rep = sched.run(trace, until=600.0)
    finally:
        remove_dispatch_hook(total_outage)
    assert rep.anomalies.get("solver_floor", 0) >= 1
    assert rep.solver_backends.get("last-known-good", 0) >= 1
    assert rep.degraded_solves == rep.n_solves  # every solve floored
    assert rep.jobs_finished == 2  # equal-share floor still makes progress


def test_floor_reuses_last_known_good_shares():
    calls = {"n": 0}

    def outage_after_first(program, backend, W, m):
        calls["n"] += 1
        if calls["n"] > 1:
            raise BackendError("late outage")

    trace = [
        _join(0.0, "t0", (1.0, 2.0)), _submit(0.0, "t0", "j0", work=1e4),
        _join(0.0, "t1", (1.0, 3.0)), _submit(0.0, "t1", "j1", work=1e4),
        # a (valid) profile change bumps the epoch so the re-solve cannot
        # reuse the previous allocation and must dispatch -> hits the outage
        _profile(100.0, "t0", (1.5, 2.0)),
    ]
    sched = OnlineScheduler(_CLUSTER2, "oef-noncoop", min_resolve_interval_s=1.0)
    add_dispatch_hook(outage_after_first)
    try:
        sched.run(trace, until=300.0)
    finally:
        remove_dispatch_hook(outage_after_first)
    good = next(s for s in sched.metrics.solves if not s.degraded)
    floored = [s for s in sched.metrics.solves if s.backend == "last-known-good"]
    assert good and floored
    # the floor reused the solved shares: estimates survive the outage
    assert sched._last_good is not None


# ---------------------------------------------------------------------------
# chaos engine
# ---------------------------------------------------------------------------


def test_chaos_trace_deterministic_and_paired():
    cluster = default_cluster("paper")
    base = synthetic_trace(4, cluster=cluster, duration_s=3600.0,
                           host_failures_per_hour=2.0, seed=5)
    t1 = ChaosEngine(standard_plan(seed=9), cluster).chaos_trace(base)
    t2 = ChaosEngine(standard_plan(seed=9), cluster).chaos_trace(base)
    key = [(e.time, e.kind.value, e.tenant, e.job_id, repr(e.payload))
           for e in t1]
    assert key == [(e.time, e.kind.value, e.tenant, e.job_id, repr(e.payload))
                   for e in t2]
    assert len(t1) > len(base)
    assert validate_host_pairing(
        [e for e in t1 if e.kind in (EventKind.HOST_FAIL,
                                     EventKind.HOST_RECOVER)]) == []


def test_chaos_same_timestamp_burst():
    cluster = default_cluster("paper")
    base = synthetic_trace(4, cluster=cluster, duration_s=3600.0, seed=5)
    plan = FaultPlan(seed=1, storms=1, storm_size=3, storm_span_s=0.0,
                     corrupt_profiles=0, solver_faults=())
    trace = ChaosEngine(plan, cluster).chaos_trace(base)
    fails = [e for e in trace if e.kind == EventKind.HOST_FAIL]
    assert len(fails) == 3
    assert len({e.time for e in fails}) == 1  # one correlated burst instant


def test_standard_storm_completes_with_zero_unhandled_exceptions():
    cluster = default_cluster("paper")
    base = synthetic_trace(6, cluster=cluster, duration_s=3600.0,
                           host_failures_per_hour=2.0, seed=3)
    engine = ChaosEngine(standard_plan(seed=7), cluster)
    trace = engine.chaos_trace(base)
    sched = OnlineScheduler(cluster, "oef-coop", solver_max_retries=1)
    with engine.installed():
        rep = sched.run(list(trace))  # must not raise
    s = engine.summary()
    assert s["solver_faults_fired"] == len(standard_plan(seed=7).solver_faults)
    assert rep.degraded_solves >= s["stats"]["crash"] + s["stats"]["timeout"]
    assert any(e["action"] == "quarantine" for e in rep.quarantine_events)
    assert any(e["action"] == "release" for e in rep.quarantine_events)
    # the transient faults were retried on the same backend, so chaos still
    # produced most answers and fell back only for crash/timeout faults
    assert rep.solver_backends.get("chaos", 0) > 0


def test_chaos_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan(solver_faults=((1, "meteor-strike"),))
    with pytest.raises(ValueError):
        FaultPlan(corrupt_kinds=("nan", "gremlin"))


# ---------------------------------------------------------------------------
# journal + crash recovery
# ---------------------------------------------------------------------------


def _trace_chaos(seed=3, fleet="paper"):
    cluster = default_cluster(fleet)
    base = synthetic_trace(6, job_types=default_job_types(fleet),
                           cluster=cluster, duration_s=3600.0,
                           host_failures_per_hour=2.0, seed=seed)
    plan = FaultPlan(seed=7, storms=3, storm_size=3, corrupt_profiles=3,
                     solver_faults=())  # solver faults are process-local state
    return cluster, ChaosEngine(plan, cluster).chaos_trace(base)


def _run(cluster, trace, jdir=None, until=None, snapshot_every=10,
         policy="oef-coop"):
    sched = OnlineScheduler(cluster, policy, solver_max_retries=1)
    journal = Journal(jdir, snapshot_every=snapshot_every) if jdir else None
    try:
        return sched.run(list(trace), until=until, journal=journal)
    finally:
        if journal is not None:
            journal.close()


@pytest.mark.parametrize("fleet", ["paper", "tpu"])
@pytest.mark.parametrize("policy", SERVICE_POLICIES)
def test_journaling_does_not_perturb_the_run(tmp_path, policy, fleet):
    cluster, trace = _trace_chaos(fleet=fleet)
    rep_plain = _run(cluster, trace, policy=policy)
    rep_journaled = _run(cluster, trace, jdir=str(tmp_path / "j"), policy=policy)
    assert _view(rep_plain) == _view(rep_journaled)


@pytest.mark.parametrize("fleet", ["paper", "tpu"])
@pytest.mark.parametrize("policy", SERVICE_POLICIES)
def test_kill_at_midpoint_resume_is_bit_exact(tmp_path, policy, fleet):
    cluster, trace = _trace_chaos(fleet=fleet)
    ref = _run(cluster, trace, jdir=str(tmp_path / "ref"), policy=policy)

    crash_dir = str(tmp_path / "crash")
    times = sorted(e.time for e in trace)
    mid = times[len(times) // 2]
    _run(cluster, trace, jdir=crash_dir, until=mid, policy=policy)  # the "kill"
    snaps = Journal(crash_dir, snapshot_every=10).available_snapshots()
    assert snaps and snaps[0] == 0  # initial snapshot + periodic ones

    resumed = resume_scheduler(crash_dir, list(trace), snapshot_every=10)
    assert _view(ref) == _view(resumed)


def test_recover_restores_pending_internals(tmp_path):
    cluster, trace = _trace_chaos()
    jdir = str(tmp_path / "j")
    times = sorted(e.time for e in trace)
    _run(cluster, trace, jdir=jdir, until=times[len(times) // 2])
    sched, journal, n_applied = recover_scheduler(jdir, snapshot_every=10)
    assert 0 < n_applied <= len(trace)
    assert journal.n_applied <= n_applied  # cursor rewound to the snapshot
    # snapshotted queue internals (predicted finishes / RESOLVE timers)
    # travel with the journal, not the trace
    internals = journal.pending_internals
    assert all(ev.kind in (EventKind.JOB_FINISH, EventKind.RESOLVE)
               for ev in internals)
    assert sched.tenants and sched.jobs


def test_journal_divergence_detected(tmp_path):
    cluster, trace = _trace_chaos()
    jdir = str(tmp_path / "j")
    _run(cluster, trace, jdir=jdir, until=1000.0)
    journal = Journal(jdir, snapshot_every=10)
    first = journal.events(0, 1)[0]
    journal.record(first)  # verify-mode replay of the journaled event: fine
    with pytest.raises(RuntimeError, match="journal divergence"):
        journal.record(dataclasses.replace(first, time=first.time + 1.0))


def test_snapshot_commit_is_atomic(tmp_path):
    cluster, trace = _trace_chaos()
    jdir = str(tmp_path / "j")
    _run(cluster, trace, jdir=jdir, until=2000.0)
    assert not any(n.endswith(".tmp") for n in os.listdir(jdir))


# ---------------------------------------------------------------------------
# host outage mid-job -> recovery -> completion
# ---------------------------------------------------------------------------


def test_host_outage_mid_job_credits_work_exactly_once():
    """A host is lost mid-job and recovers: the service sees the incident as
    a HOST_FAIL/HOST_RECOVER pair and its delivered-work accounting credits
    the job's work exactly once."""
    cluster = ClusterSpec(types=("g",), m=(4,))
    total_work = 1000.0
    trace = [
        Event(0.0, EventKind.TENANT_JOIN, tenant="team", payload={
            "job_types": [{"name": "train", "speedup": [1.0]}]}),
        Event(0.0, EventKind.JOB_SUBMIT, tenant="team", job_id="run1",
              payload={"job_type": "train", "workers": 4,
                       "total_work": total_work}),
        Event(100.0, EventKind.HOST_FAIL, payload={"type": 0, "host": 0}),
        Event(400.0, EventKind.HOST_RECOVER, payload={"type": 0, "host": 0}),
    ]
    sched = OnlineScheduler(cluster, "oef-noncoop", min_resolve_interval_s=1.0)
    rep = sched.run(trace)
    job = sched.jobs["run1"]
    assert job.finished and rep.jobs_finished == 1
    # exactly-once accounting: no progress credited during the outage, no
    # double-credit after the restore
    assert rep.tenant_delivered_work["team"] == pytest.approx(total_work)
    assert job.finish_time > 400.0  # the outage pushed the finish past recovery
