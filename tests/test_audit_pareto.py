"""The fairness audit against the per-user formulation it replaced.

``pareto_improvement_value(within=None)`` solves its LP over the distinct
rows of W, built sparse; ``property_report`` computes each matrix once.
Here both are held to test-local copies of the per-user formulation:

  - the merged LP's value equals the dense per-user LP's within 1e-9
    (relative above 1), with the same ``pareto_efficient`` verdict, on one
    row, the paper's six rows among 64 and 1024 users, self-profiled TPU
    rows all or mostly distinct, and cooperative allocations that are not
    globally Pareto efficient; under HiGHS and, at small n, the simplex;
  - ``property_report`` returns exactly the per-user report;
  - a traced replay's ``audit`` spans carry ``tenants`` and ``pareto_rows``.
"""
import functools

import numpy as np
import pytest

from repro import obs
from repro.core import oef, properties
from repro.core.lp import solve_lp
from repro.core.profiler import PAPER_WORKLOAD_SPEEDUPS, ProfilingAgent
from repro.core.types import TPU_FLEET
from repro.service.scheduler import OnlineScheduler
from repro.service.traces import (TPU_WORKLOADS, default_cluster,
                                  default_job_types, synthetic_trace)

SEEDS = [0, 1, 2]
#: the counterexample of test_oef_properties.test_coop_global_pe_counterexample
COOP_W = np.array([
    [1.0, 6.091, 10.771],
    [1.0, 1.609, 1.934],
    [1.0, 2.142, 2.515],
    [1.0, 1.837, 3.500],
    [1.0, 9.424, 16.585],
])
COOP_M = np.array([8.0, 5.0, 1.0])


def reference_pareto_value(W, X, m, method="highs"):
    """The per-user global Pareto LP, dense: max sum_l s_l subject to
    W_l.x'_l >= W_l.x_l + s_l and capacity."""
    W = np.asarray(W, dtype=np.float64)
    X = np.asarray(X, dtype=np.float64)
    m = np.asarray(m, dtype=np.float64)
    n, k = W.shape
    own = np.einsum("lk,lk->l", W, X)
    nv = n * k + n
    c = np.concatenate([np.zeros(n * k), np.ones(n)])
    A_cap = np.zeros((k, nv))
    for j in range(k):
        A_cap[j, j : n * k : k] = 1.0
    rows = np.zeros((n, nv))
    for l in range(n):
        rows[l, l * k : (l + 1) * k] = -W[l]
        rows[l, n * k + l] = 1.0
    res = solve_lp(c, np.vstack([A_cap, rows]), np.concatenate([m, -own]),
                   method=method)
    return float(res.fun) if res.ok else float("inf")


def reference_report(W, X, m):
    """The audit as one call per property, each matrix built anew."""
    return {
        "envy_free": properties.is_envy_free(W, X),
        "sharing_incentive": properties.is_sharing_incentive(W, X, m),
        "pareto_efficient": reference_pareto_value(W, X, m) <= 1e-5,
        "adjacent_types": properties.adjacency_ok(X),
        "total_efficiency": properties.total_efficiency(W, X),
        "max_envy": float(np.max(properties.envy_matrix(W, X))),
        "min_si_slack": float(np.min(properties.sharing_incentive_slack(W, X, m))),
    }


def draw_users(rows, n, seed):
    """``n`` users over ``rows``: each row at least once, the rest drawn."""
    rng = np.random.default_rng(seed)
    pick = np.concatenate([np.arange(len(rows)),
                           rng.integers(0, len(rows), n - len(rows))])
    return rows[rng.permutation(pick)]


def paper_rows(n, seed):
    """``n`` users over the paper's six workload rows (k=3)."""
    rows = np.array(list(PAPER_WORKLOAD_SPEEDUPS.values()), dtype=np.float64)
    return draw_users(rows, n, seed), default_cluster("paper").m_vec * (n // 8)


def tpu_rows(n_rows, n, seed):
    """``n`` users over ``n_rows`` distinct self-profiled TPU rows (k=4)."""
    agent = ProfilingAgent(TPU_FLEET, error_pct=0.05, seed=100 + seed)
    rows = np.array([agent.profile(TPU_WORKLOADS[i % len(TPU_WORKLOADS)]).speedup
                     for i in range(n_rows)])
    return draw_users(rows, n, seed), default_cluster("tpu").m_vec * (n // 16)


def slack_allocation(W, m, seed):
    """A feasible allocation that leaves capacity and mixes types badly."""
    X = np.random.default_rng(seed).uniform(0.0, 1.0, W.shape)
    return X * (0.9 * m / X.sum(axis=0))


@functools.lru_cache(maxsize=None)
def instance(pattern, seed):
    """(W, X, m) of one row pattern; ``seed`` permutes and redraws."""
    rng = np.random.default_rng(seed)
    if pattern == "one-row":
        W = np.tile([1.0, 1.6, 2.3], (24, 1))
        m = np.array([8.0, 8.0, 8.0])
        return W, slack_allocation(W, m, seed), m
    if pattern.startswith("paper-"):
        n, alloc = pattern.split("-")[1:]
        W, m = paper_rows(int(n), seed)
    elif pattern.startswith("distinct-"):
        alloc = pattern.split("-")[1]
        W, m = tpu_rows(32, 32, seed)
    elif pattern.startswith("tpu-"):  # mostly distinct, as in the TPU cell
        alloc = pattern.split("-")[1]
        W, m = tpu_rows(36, 48, seed)
    elif pattern.startswith("coop-global"):
        W = COOP_W[rng.permutation(len(COOP_W))]
        if pattern.endswith("-twice"):
            W = np.vstack([W, W])
        m = COOP_M * (2 if pattern.endswith("-twice") else 1)
        return W, oef.solve_coop(W, m).X, m
    else:
        raise ValueError(pattern)
    X = oef.solve_noncoop(W, m).X if alloc == "opt" else slack_allocation(W, m, seed)
    return W, X, m


#: (pattern, methods): the dense simplex runs at small n only
PATTERNS = [
    ("one-row", ("highs", "simplex")),
    ("paper-64-opt", ("highs", "simplex")),
    ("paper-64-slack", ("highs", "simplex")),
    ("paper-1024-opt", ("highs",)),
    ("paper-1024-slack", ("highs",)),
    ("distinct-opt", ("highs", "simplex")),
    ("distinct-slack", ("highs", "simplex")),
    ("tpu-opt", ("highs", "simplex")),
    ("tpu-slack", ("highs", "simplex")),
    ("coop-global", ("highs", "simplex")),
    ("coop-global-twice", ("highs", "simplex")),
]
CASES = [(p, meth) for p, methods in PATTERNS for meth in methods]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("pattern,method", CASES)
def test_merged_pareto_lp_matches_per_user_lp(pattern, method, seed):
    W, X, m = instance(pattern, seed)
    got = properties.pareto_improvement_value(W, X, m, method=method)
    want = reference_pareto_value(W, X, m, method=method)
    assert abs(got - want) <= 1e-9 * max(1.0, abs(want)), (got, want)
    assert (got <= 1e-5) == (want <= 1e-5)
    if pattern.startswith("coop-global"):
        assert want > 0.1  # not globally Pareto efficient
    if pattern.endswith("-opt"):
        assert want <= 1e-5  # the non-cooperative optimum is


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("pattern", ["paper-64-opt", "paper-64-slack",
                                     "tpu-opt", "tpu-slack",
                                     "coop-global-twice"])
def test_property_report_equals_per_user_report(pattern, seed):
    W, X, m = instance(pattern, seed)
    stats = {}
    got = properties.property_report(W, X, m, stats=stats)
    want = reference_report(W, X, m)
    assert got == want
    assert [type(v) for v in got.values()] == [type(v) for v in want.values()]
    assert stats["pareto_rows"] == len(np.unique(W, axis=0))


@pytest.mark.parametrize("cluster_kind", ["paper", "tpu"])
def test_audit_spans_carry_tenants_and_pareto_rows(cluster_kind):
    cluster = default_cluster(cluster_kind)
    trace = synthetic_trace(12, job_types=default_job_types(cluster_kind),
                            cluster=cluster, duration_s=3600.0, seed=18)
    tracer = obs.Tracer()
    obs.set_tracer(tracer)
    try:
        rep = OnlineScheduler(cluster, "oef-noncoop", audit_every=2,
                              min_resolve_interval_s=1.0).run(trace)
    finally:
        obs.set_tracer(None)
    audits = [args for (name, _c, _p, _t0, _d, _s, args) in tracer.spans
              if name == "audit"]
    assert audits and len(audits) == len(rep.fairness_audits)
    for args in audits:
        assert 1 <= args["pareto_rows"] <= args["tenants"]
    if cluster_kind == "paper":  # six rows among twelve tenants: merged
        assert any(a["pareto_rows"] < a["tenants"] for a in audits)
