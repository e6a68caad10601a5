"""The comparison that decides ``correct``.

Two layers, both judged on what the measured window produced:

1. The solve tier: a sample of the window's decisions, drawn from the seed
   with every decision that solved anew ahead of the reused ones, is solved
   again by the configuration's plain reference
   (``bench/references/<policy>.py``) and the program's allocation is held
   to the limits the configuration file states.
2. Rounding and placement: every grant of every decision in the window is
   checked against the fleet and the trace, exactly (limit 0): devices per
   type and per host within capacity, none on a down host, every placed job
   given exactly its gang and owned by a tenant the decision covered.

The instance each decision solved is rebuilt from the benchmark's own
trace and configuration: the tenants' speedups and the capacity are not
taken from the program. The control, the reference's optimum held in
float32 (HiGHS solves in float64 only), is read beside the program by
``bench/control.py``.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Dict, Iterable, List, Mapping, Sequence, Set, Tuple

import numpy as np

from repro.service.events import Event, EventKind

REFERENCES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "references")


def reference(policy: str):
    """The reference module ``bench/references/<policy>.py``."""
    path = os.path.join(REFERENCES, policy + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + policy.replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reference {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Trace:
    """What the reference knows of the trace: each tenant's speedup row,
    each job's owner and gang, and the hosts' fail/recover history."""

    def __init__(self, events: Iterable[Event]):
        self.rows: Dict[str, np.ndarray] = {}
        self.jobs: Dict[str, Tuple[str, int, float]] = {}
        self.churn: List[Tuple[float, bool, Tuple[int, int]]] = []
        for ev in events:
            if ev.kind == EventKind.TENANT_JOIN:
                jts = ev.payload["job_types"]
                if len(jts) != 1 or float(ev.payload["weight"]) != 1.0:
                    raise ValueError("the references cover tenants of weight 1 "
                                     "with one job type")
                self.rows[ev.tenant] = np.asarray(jts[0]["speedup"], dtype=np.float64)
            elif ev.kind == EventKind.JOB_SUBMIT:
                self.jobs[ev.job_id] = (ev.tenant, int(ev.payload["workers"]), ev.time)
            elif ev.kind in (EventKind.HOST_FAIL, EventKind.HOST_RECOVER):
                self.churn.append((ev.time, ev.kind == EventKind.HOST_FAIL,
                                   (int(ev.payload["type"]), int(ev.payload["host"]))))

    def down_at(self, t: float) -> Set[Tuple[int, int]]:
        down: Set[Tuple[int, int]] = set()
        for when, fail, pair in self.churn:
            if when > t:
                break
            (down.add if fail else down.discard)(pair)
        return down

    def instance(self, tenants: Sequence[str], config: Mapping,
                 t: float) -> Tuple[np.ndarray, np.ndarray]:
        """The decision's (W, m): speedup rows in solve order and the
        capacity left by the hosts down at ``t``."""
        W = np.stack([self.rows[name] for name in tenants])
        per_host = int(config["devices_per_host"])
        m = np.asarray(config["devices_per_type"], dtype=np.float64)
        for j, h in self.down_at(t):
            m[j] -= min(per_host, max(0, int(config["devices_per_type"][j]) - h * per_host))
        return W, m


def grant_violations(decision, trace: Trace, config: Mapping) -> int:
    """Count of broken placement rules in one decision's grants."""
    counts = [int(x) for x in config["devices_per_type"]]
    per_host = int(config["devices_per_host"])
    down = trace.down_at(decision.sim_t)
    covered = set(decision.tenants)
    used: Dict[Tuple[int, int], int] = {}
    bad = 0
    for job_id, placed in decision.assignments.items():
        owner = trace.jobs.get(job_id)
        if owner is None or owner[0] not in covered or owner[2] > decision.sim_t:
            bad += 1
            continue
        if sum(c for _, _, c in placed) != owner[1]:
            bad += 1
        for j, h, c in placed:
            if not (0 <= j < len(counts)) or not (0 <= h * per_host < counts[j]) \
                    or c < 1 or (j, h) in down:
                bad += 1
                continue
            used[(j, h)] = used.get((j, h), 0) + c
    for (j, h), c in used.items():
        if c > min(per_host, counts[j] - h * per_host):
            bad += 1
    for j, mj in enumerate(counts):
        if sum(c for (jj, _), c in used.items() if jj == j) > mj:
            bad += 1
    return bad


def sample(decisions: Sequence, k: int, seed: int) -> List:
    """Up to ``k`` decisions that solved, drawn from ``seed``, every fresh
    solve ahead of the reused ones."""
    solved = [d for d in decisions if d.X is not None]
    keys = np.random.default_rng([int(seed), 1]).random(len(solved))
    order = sorted(range(len(solved)),
                   key=lambda i: (bool(solved[i].record.reused), keys[i]))
    return [solved[i] for i in order[:k]]


def compare(decisions: Sequence, trace: Trace, config: Mapping, seed: int
            ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Largest reading of each reference number over the sampled decisions,
    for the program's allocations and for the control: the reference's own
    optimum held in float32, put in the program's place."""
    ref = reference(str(config["policy"]))
    program: Dict[str, float] = {}
    control: Dict[str, float] = {}
    for d in sample(decisions, int(config["reference_sample"]), seed):
        W, m = trace.instance(d.tenants, config, d.sim_t)
        X_ref = ref.solve(W, m)
        X_ctl = X_ref.astype(np.float32).astype(np.float64)
        for out, X in ((program, d.X), (control, X_ctl)):
            if X.shape != W.shape:
                out["shape_mismatch"] = out.get("shape_mismatch", 0.0) + 1.0
                continue
            for name, value in ref.numbers(W, m, X, X_ref).items():
                out[name] = max(out.get(name, 0.0), value)
    return program, control
