"""The one traffic generator: a seeded Philly-like trace of world events.

A copy of ``repro.service.traces.synthetic_trace`` (with ``_submit``,
``paired_host_churn`` and ``validate_host_pairing``), kept with the benchmark
so that a change to the program's own generator cannot move what the
benchmark replays. It emits the program's input interface,
``repro.service.events.Event``. Each traffic mix is a JSON file of this
generator's parameters beside this module; the configuration supplies the
tenant count, the job-type catalog and the fleet.

Every run of a mix replays the same work: the trace is drawn once from the
mix's ``base_seed``, and a run's ``--seed`` only permutes which tenant gets
which stream of jobs (names, registration order, tie-breaks). Drawn afresh
per seed, the work itself moved ``events_per_s`` by some 10% between seeds
where two runs of one seed agreed within about 2%.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

from repro.service.events import Event, EventKind

#: traffic-file keys passed through to :func:`synthetic_trace`.
GENERATOR_KEYS = ("duration_s", "mean_interarrival_s", "jobs_at_join",
                  "mean_work_s", "workers_choices", "weight_choices",
                  "join_spread_s", "host_failures_per_hour", "mean_outage_s")


class JobType:
    """A catalog entry: name, speedup vector over the device types, and the
    smallest gang it runs on."""

    __slots__ = ("name", "speedup", "min_demand")

    def __init__(self, name: str, speedup: Sequence[float], min_demand: int = 1):
        self.name = name
        self.speedup = tuple(float(s) for s in speedup)
        self.min_demand = int(min_demand)


def generate(config: Mapping, traffic: Mapping, seed: int) -> List[Event]:
    """The trace of one run: the configuration's tenants and catalog under
    the traffic mix's arrival parameters, drawn from the mix's ``base_seed``,
    with the tenants permuted by ``seed``."""
    job_types = [JobType(**jt) for jt in config["job_types"]]
    params = {k: traffic[k] for k in GENERATOR_KEYS if k in traffic}
    n = int(config["tenants"])
    events = synthetic_trace(
        n, job_types=job_types, device_counts=config["devices_per_type"],
        devices_per_host=int(config["devices_per_host"]),
        seed=int(traffic["base_seed"]), **params)
    return permute_tenants(events, np.random.default_rng(seed).permutation(n))


def permute_tenants(events: Sequence[Event], perm: Sequence[int]) -> List[Event]:
    """Give tenant ``i``'s events to tenant ``perm[i]``, job ids with them,
    and order same-time events as the generator orders them: tenants by
    index, each in its own order, host churn last."""
    n = len(perm)
    keyed = []
    for pos, ev in enumerate(events):
        if ev.tenant:
            new = int(perm[int(ev.tenant[len("tenant"):])])
            name = f"tenant{new}"
            job_id = name + ev.job_id[len(ev.tenant):] if ev.job_id else ""
            ev = dataclasses.replace(ev, tenant=name, job_id=job_id)
        else:
            new = n
        keyed.append((ev.time, new, pos, ev))
    keyed.sort(key=lambda k: k[:3])
    return [k[3] for k in keyed]


def _job_type_payload(jt: JobType) -> Dict[str, object]:
    return {"name": jt.name, "speedup": [float(s) for s in jt.speedup],
            "min_demand": int(jt.min_demand)}


def synthetic_trace(
    n_tenants: int,
    *,
    job_types: Sequence[JobType],
    device_counts: Optional[Sequence[int]] = None,
    duration_s: float = 7200.0,
    mean_interarrival_s: float = 600.0,
    jobs_at_join: int = 3,
    mean_work_s: float = 1800.0,
    workers_choices: Sequence[int] = (1, 1, 2, 4),
    weight_choices: Sequence[float] = (1.0,),
    join_spread_s: float = 0.0,
    host_failures_per_hour: float = 0.0,
    mean_outage_s: float = 600.0,
    devices_per_host: int = 4,
    seed: int = 0,
) -> List[Event]:
    """Seeded Philly-like trace: tenant joins, job arrival streams, failures."""
    rng = np.random.default_rng(seed)
    job_types = list(job_types)
    events: List[Event] = []
    for i in range(n_tenants):
        name = f"tenant{i}"
        jt = job_types[int(rng.integers(len(job_types)))]
        weight = float(rng.choice(np.asarray(weight_choices, dtype=np.float64)))
        join_t = float(rng.uniform(0.0, join_spread_s)) if join_spread_s > 0 else 0.0
        events.append(Event(join_t, EventKind.TENANT_JOIN, tenant=name, payload={
            "weight": weight, "job_types": [_job_type_payload(jt)]}))
        q = 0
        for _ in range(jobs_at_join):
            events.append(_submit(join_t, name, jt, q, rng, workers_choices, mean_work_s))
            q += 1
        t = join_t
        while True:
            t += float(rng.exponential(mean_interarrival_s))
            if t >= duration_s:
                break
            events.append(_submit(t, name, jt, q, rng, workers_choices, mean_work_s))
            q += 1
    if host_failures_per_hour > 0:
        if device_counts is None:
            raise ValueError("host_failures_per_hour needs the fleet's device counts")
        events.extend(paired_host_churn(
            device_counts, duration_s=duration_s,
            failures_per_hour=host_failures_per_hour,
            mean_outage_s=mean_outage_s,
            devices_per_host=devices_per_host, rng=rng))
    events.sort(key=lambda e: e.time)  # stable: same-time order = generation order
    bad = validate_host_pairing(events)
    if bad:
        raise RuntimeError(f"generated trace has unpaired host churn: {bad}")
    return events


def paired_host_churn(
    device_counts: Sequence[int],
    *,
    duration_s: float,
    failures_per_hour: float,
    mean_outage_s: float,
    devices_per_host: int = 4,
    rng: np.random.Generator,
) -> List[Event]:
    """Per-host alternating FAIL/RECOVER renewal churn, strictly paired: the
    next failure clock starts only after the recovery, and an outage that
    outlives ``duration_s`` still emits its RECOVER."""
    events: List[Event] = []
    rate = failures_per_hour / 3600.0
    for j, mj in enumerate(device_counts):
        n_hosts = int(np.ceil(mj / devices_per_host))
        for h in range(n_hosts):
            t = float(rng.exponential(1.0 / rate))
            while t < duration_s:
                up = t + float(rng.exponential(mean_outage_s))
                events.append(Event(t, EventKind.HOST_FAIL,
                                    payload={"type": j, "host": h}))
                events.append(Event(up, EventKind.HOST_RECOVER,
                                    payload={"type": j, "host": h}))
                t = up + float(rng.exponential(1.0 / rate))
    return events


def validate_host_pairing(events: Sequence[Event]) -> List[str]:
    """HOST_FAIL/HOST_RECOVER alternation per host in time order; returns the
    violations (empty when clean)."""
    violations: List[str] = []
    down: set = set()
    for ev in sorted(events, key=lambda e: e.time):
        if ev.kind == EventKind.HOST_FAIL:
            pair = (int(ev.payload["type"]), int(ev.payload["host"]))
            if pair in down:
                violations.append(
                    f"t={ev.time}: host {pair} re-failed while already down")
            down.add(pair)
        elif ev.kind == EventKind.HOST_RECOVER:
            pair = (int(ev.payload["type"]), int(ev.payload["host"]))
            if pair not in down:
                violations.append(
                    f"t={ev.time}: host {pair} recovered while not down")
            down.discard(pair)
    for pair in sorted(down):
        violations.append(f"host {pair} failed but never recovers in-stream")
    return violations


def _submit(t, tenant, jt, q, rng, workers_choices, mean_work_s) -> Event:
    return Event(t, EventKind.JOB_SUBMIT, tenant=tenant, job_id=f"{tenant}-j{q}",
                 payload={"job_type": jt.name,
                          "workers": int(rng.choice(np.asarray(workers_choices))),
                          "total_work": float(rng.exponential(mean_work_s)) + 60.0})
