"""Every grant of every decision is feasible, under every policy and fleet.

``OnlineScheduler`` turns fair shares into whole-device grants on hosts.
After each decision this test holds the grants to the fleet and to the
trace, from state it tracks itself out of the trace (host outages, tenant
presence, job gangs and owners), not from the scheduler's own bookkeeping:

  - per type, no more devices granted than the surviving hosts hold;
  - per host, at most ``devices_per_host`` (fewer on a short last host);
  - no grant on a host that is down;
  - every job granted exactly its gang, or nothing (and then no rate);
  - every grant owned by an unfinished job of a present tenant, submitted
    by the decision's time.

At the end of the run the work credited to the tenants equals the work
the jobs did. Traces: steady submits, host failures, and tenant churn
(a tenant leaves, another's profile changes, a third joins late).
"""
import math
from typing import Dict, List, Set, Tuple

import pytest

from repro.service.events import Event, EventKind
from repro.service.scheduler import SERVICE_POLICIES, OnlineScheduler
from repro.service.traces import default_cluster, default_job_types, synthetic_trace

DEVICES_PER_HOST = 4
N_TENANTS = 6
DURATION_S = 3600.0


def _trace(fleet: str, kind: str) -> List[Event]:
    cluster = default_cluster(fleet)
    job_types = default_job_types(fleet)
    kw = dict(job_types=job_types, cluster=cluster, duration_s=DURATION_S,
              mean_interarrival_s=600.0, mean_work_s=1800.0, seed=17)
    if kind == "failures":
        return synthetic_trace(N_TENANTS, host_failures_per_hour=2.0,
                               mean_outage_s=300.0, **kw)
    events = synthetic_trace(N_TENANTS, **kw)
    if kind == "churn":
        joins = {e.tenant: e for e in events if e.kind == EventKind.TENANT_JOIN}
        jt = joins["tenant1"].payload["job_types"][0]
        events = events + [
            Event(900.0, EventKind.PROFILE_UPDATE, tenant="tenant1", payload={
                "job_type": jt["name"],
                "speedup": [1.0] + [1.5 * s for s in jt["speedup"][1:]]}),
            Event(1200.0, EventKind.TENANT_LEAVE, tenant="tenant2"),
            Event(1500.0, EventKind.TENANT_JOIN, tenant="late",
                  payload={"weight": 1.0, "job_types": [jt]}),
            Event(1500.0, EventKind.JOB_SUBMIT, tenant="late", job_id="late-j0",
                  payload={"job_type": jt["name"], "workers": 2,
                           "total_work": 900.0}),
        ]
        events.sort(key=lambda e: e.time)
    return events


class _TraceState:
    """What the trace says at a decision's time: down hosts, present
    tenants, and each submitted job's owner, gang and submit time."""

    def __init__(self, events: List[Event]):
        self._events = sorted(events, key=lambda e: e.time)
        self._next = 0
        self.down: Set[Tuple[int, int]] = set()
        self.present: Set[str] = set()
        self.jobs: Dict[str, Tuple[str, int, float]] = {}

    def at(self, t: float) -> "_TraceState":
        """Apply every trace event at or before ``t`` (a decision runs after
        all events of its instant)."""
        while self._next < len(self._events) and self._events[self._next].time <= t:
            ev = self._events[self._next]
            self._next += 1
            if ev.kind == EventKind.HOST_FAIL:
                self.down.add((int(ev.payload["type"]), int(ev.payload["host"])))
            elif ev.kind == EventKind.HOST_RECOVER:
                self.down.discard((int(ev.payload["type"]), int(ev.payload["host"])))
            elif ev.kind == EventKind.TENANT_JOIN:
                self.present.add(ev.tenant)
            elif ev.kind == EventKind.TENANT_LEAVE:
                self.present.discard(ev.tenant)
            elif ev.kind == EventKind.JOB_SUBMIT:
                self.jobs[ev.job_id] = (ev.tenant, int(ev.payload["workers"]), ev.time)
        return self


def _check_decision(sched: OnlineScheduler, world: _TraceState, now: float) -> None:
    counts = [int(x) for x in sched.cluster.m]
    grants = sched._prev_assignments or {}
    used: Dict[Tuple[int, int], int] = {}
    for job_id, placed in grants.items():
        owner, gang, submitted = world.jobs[job_id]
        job = sched.jobs[job_id]
        assert owner in world.present, (now, job_id, owner)
        assert submitted <= now and not job.finished, (now, job_id)
        assert sum(c for _, _, c in placed) == gang, (now, job_id, placed)
        assert job.assignment == tuple(sorted(placed)) and job.rate > 0, (now, job_id)
        for j, h, c in placed:
            assert 0 <= j < len(counts) and 0 <= h * DEVICES_PER_HOST < counts[j]
            assert c >= 1 and (j, h) not in world.down, (now, job_id, (j, h))
            used[(j, h)] = used.get((j, h), 0) + c
    for (j, h), c in used.items():
        assert c <= min(DEVICES_PER_HOST, counts[j] - h * DEVICES_PER_HOST), (now, j, h)
    for j, mj in enumerate(counts):
        lost = sum(min(DEVICES_PER_HOST, mj - h * DEVICES_PER_HOST)
                   for (jj, h) in world.down if jj == j)
        assert sum(c for (jj, _), c in used.items() if jj == j) <= mj - lost, (now, j)
    for job in sched.jobs.values():  # a gang or nothing: no grant, no rate
        if job.job_id not in grants and not job.finished:
            assert job.rate == 0.0, (now, job.job_id)


@pytest.mark.parametrize("kind", ["steady", "failures", "churn"])
@pytest.mark.parametrize("fleet", ["paper", "tpu"])
@pytest.mark.parametrize("policy", SERVICE_POLICIES)
def test_every_decision_grants_are_feasible(policy, fleet, kind):
    events = _trace(fleet, kind)
    sched = OnlineScheduler(default_cluster(fleet), policy,
                            devices_per_host=DEVICES_PER_HOST)
    world = _TraceState(events)
    on_solve = sched.metrics.on_solve
    checked = []

    def checking_on_solve(rec):
        _check_decision(sched, world.at(rec.time), rec.time)
        checked.append((rec.time, len(world.down)))
        on_solve(rec)

    sched.metrics.on_solve = checking_on_solve
    rep = sched.run(events)
    assert checked and rep.n_solves == len(checked)
    if kind == "failures":  # some decisions ran with hosts down
        assert any(down for _, down in checked)
    if kind == "churn":  # and some after the leave, the update and the join
        assert sched.tenants["tenant2"].left_at == 1200.0
        assert sched.jobs["late-j0"].first_scheduled is not None
        assert any(t >= 1500.0 for t, _ in checked)
    # the work credited to the tenants is the work the jobs did
    done = sum(job.done for job in sched.jobs.values())
    assert math.isclose(sum(rep.tenant_delivered_work.values()), done,
                        rel_tol=1e-12, abs_tol=1e-9)
    for job in sched.jobs.values():
        assert job.done <= job.total_work
        if job.finished:
            assert job.done == job.total_work
    assert math.isfinite(done) and done > 0
