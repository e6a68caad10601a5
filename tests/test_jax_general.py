"""The jax tier's general non-cooperative solve against the plain reference.

Off the (piecewise-)Monge staircase class ``oef-noncoop``'s jax backend runs
the certified dual-price search of ``core.jax_general``. It is held to the
scipy LP of ``oef.solve_noncoop`` on seeded off-class instances, within the
limits the TPU-fleet benchmark configuration states; degenerate instances
(identical rows, a type with no capacity, a slowest type other than the
first), warm starts and the in-class water-filling path are covered, and a
TPU-fleet replay through ``OnlineScheduler`` never leaves the jax tier.
"""
import json
import os

import numpy as np
import pytest

from repro.core import backends, jax_general, oef
from repro.core.profiler import ProfilingAgent
from repro.core.types import TPU_FLEET, ClusterSpec
from repro.service import OnlineScheduler
from repro.service.events import Event, EventKind
from repro.service.traces import TPU_WORKLOADS, synthetic_trace

CONFIG = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                      "tpu4-noncoop-1024.json")


@pytest.fixture(scope="module")
def limits():
    with open(CONFIG) as f:
        return json.load(f)["limits"]


def reference_numbers(W, m, X):
    """The benchmark's comparison numbers against the scipy LP optimum."""
    X_ref = oef.solve_noncoop(W, m).X
    t = np.einsum("lk,lk->l", W, X)
    opt = float((W * X_ref).sum())
    return {"obj_gap": abs(float(t.sum()) - opt) / opt,
            "tput_spread": float(np.abs(t - t.mean()).max()) / float(t.mean()),
            "cap_excess": max(0.0, float(((X.sum(axis=0) - m)
                                          / np.maximum(m, 1e-300))[m > 0].max()))}


def assert_matches_reference(W, m, limits, **kw):
    alloc = backends.dispatch("oef-noncoop", W, m, backend="jax", **kw)
    assert alloc.meta["backend"] == "jax"
    assert "fallback_reason" not in alloc.meta
    assert alloc.meta["instance_class"] == "general"
    assert alloc.meta["search_iters"] > 0
    assert np.all(alloc.X >= 0) and np.all(alloc.X[:, m <= 0] == 0)
    for name, value in reference_numbers(W, m, alloc.X).items():
        assert value <= limits[name], (name, value)
    return alloc


def profiled_rows(n, k, rng, error=0.05):
    """``n`` rows drawn from the TPU catalog's workloads, profiled with
    ``error`` on the fleet's first ``k`` generations."""
    agent = ProfilingAgent(TPU_FLEET[:k], error_pct=error,
                           seed=int(rng.integers(2**31)))
    costs = [TPU_WORKLOADS[i] for i in rng.integers(len(TPU_WORKLOADS), size=n)]
    return np.array([agent.profile(c).speedup for c in costs])


def off_class(n, k, seed):
    rng = np.random.default_rng(seed)
    while True:
        W = rng.uniform(1.0, 4.0, size=(n, k))
        if oef.classify_staircase(W) is None:
            return W, rng.integers(1, 3 * n, size=k).astype(float)


@pytest.mark.parametrize("k", [3, 4])
@pytest.mark.parametrize("n", [8, 32, 128])
@pytest.mark.parametrize("seed", range(3))
def test_general_solve_matches_the_lp(n, k, seed, limits):
    W, m = off_class(n, k, 1000 * n + 10 * k + seed)
    alloc = assert_matches_reference(W, m, limits)
    lp = oef.solve_noncoop(W, m)
    assert alloc.meta["tau"] == pytest.approx(lp.meta["tau"], rel=1e-9)


@pytest.mark.parametrize("n", [32, 128])
def test_self_profiled_tpu_rows_match_the_lp(n, limits):
    rng = np.random.default_rng(n)
    W = profiled_rows(n, 4, rng)
    assert oef.classify_staircase(W) is None
    assert_matches_reference(W, np.array([16.0, 16.0, 8.0, 8.0]), limits)


def test_identical_rows_tie_their_prices(limits):
    """Tenants with one profile are one row of the search: their shares are
    equal and the split between types is the LP's."""
    rng = np.random.default_rng(7)
    base, _ = off_class(5, 4, 7)
    W = base[rng.integers(5, size=60)]
    alloc = assert_matches_reference(W, np.array([30.0, 20.0, 25.0, 10.0]), limits)
    for row in base:
        same = np.all(W == row, axis=1)
        np.testing.assert_array_equal(alloc.X[same], alloc.X[same][:1].repeat(
            same.sum(), axis=0))


@pytest.mark.parametrize("seed", range(4))
def test_distinct_rows_tied_at_the_prices(seed, limits):
    """Speedups quantized to a few values make distinct rows tie exactly at
    the optimal prices, more than the ``k - 1`` a tree can split: the tied
    rows share their types by non-negative least squares."""
    rng = np.random.default_rng(seed)
    while True:
        W = rng.choice([1.0, 1.5, 2.0, 3.0], size=(48, 3))
        if oef.classify_staircase(W) is None:
            break
    assert_matches_reference(W, rng.integers(4, 12, size=3).astype(float), limits)


@pytest.mark.parametrize("dead", [[1], [0, 2]])
def test_a_type_with_no_capacity_gets_nothing(dead, limits):
    """All hosts of a type failed: its capacity is 0 and so is its share."""
    W, m = off_class(32, 4, 11)
    m[dead] = 0.0
    assert_matches_reference(W, m, limits)


def test_rows_whose_slowest_type_is_not_the_first(limits):
    """Profiling error flips v5e and v4 for the collective-heavy workload
    (1.0 against 1.084): such rows are normalized to their own slowest type
    and keep the fleet's order."""
    agent = ProfilingAgent(TPU_FLEET, error_pct=0.05, seed=3)
    rows = np.array([agent.profile(TPU_WORKLOADS[3]).speedup for _ in range(64)])
    flipped = rows.argmin(axis=1) != 0
    assert flipped.any() and (~flipped).any()
    W = np.vstack([rows, profiled_rows(64, 4, np.random.default_rng(3))])
    assert oef.classify_staircase(W) is None
    assert_matches_reference(W, np.array([20.0, 20.0, 12.0, 12.0]), limits)


def test_single_tenant_and_no_capacity():
    W, m = off_class(8, 3, 5)
    X, t, prices, _ = jax_general.solve_general(W[:1], m)
    np.testing.assert_allclose(X, m[None, :], rtol=1e-12)
    assert t == pytest.approx(float(W[0] @ m), rel=1e-12)
    X, t, prices, iters = jax_general.solve_general(W, np.zeros(3))
    assert t == 0.0 and iters == 0 and not X.any()


def test_warm_start_from_prices_gives_the_cold_answer(limits):
    """The prices of one decision start the next after tenants join and
    leave, and the answer is the cold start's."""
    rng = np.random.default_rng(21)
    W = profiled_rows(200, 4, rng)
    m = np.array([40.0, 40.0, 24.0, 24.0])
    first = assert_matches_reference(W, m, limits)
    nxt = np.vstack([W[10:], profiled_rows(7, 4, rng)])
    cold = assert_matches_reference(nxt, m, limits)
    warm = assert_matches_reference(nxt, m, limits,
                                    price_hint=first.meta["prices"])
    assert warm.meta["warm_started"] and not cold.meta["warm_started"]
    assert warm.meta["search_iters"] <= cold.meta["search_iters"]
    np.testing.assert_allclose(warm.X, cold.X, rtol=0, atol=1e-9)
    assert warm.meta["tau"] == pytest.approx(cold.meta["tau"], rel=1e-12)


def test_unusable_price_hint_starts_cold(limits):
    W, m = off_class(32, 4, 4)
    for hint in (np.ones(3), np.array([1.0, np.nan, 1.0, 1.0]),
                 np.array([1.0, 0.0, 1.0, 1.0])):
        alloc = assert_matches_reference(W, m, limits, price_hint=hint)
        assert alloc.meta["search_iters"] > 0


def test_search_that_cannot_certify_falls_back_to_the_lp(monkeypatch):
    monkeypatch.setattr(jax_general, "crossover", lambda *a, **k: None)
    W, m = off_class(16, 3, 9)
    alloc = backends.dispatch("oef-noncoop", W, m, backend="jax")
    assert alloc.meta["backend"] == "lp"
    assert "did not certify" in alloc.meta["fallback_reason"]


def test_in_class_instances_keep_the_water_filling_answer_bit_for_bit():
    rng = np.random.default_rng(2)
    a = np.cumsum(rng.uniform(0.05, 0.8, size=40)) + 1.0
    c = np.cumsum(rng.uniform(0.05, 0.6, size=4))
    W = np.power(a[:, None], (c - c[0])[None, :])
    m = np.array([10.0, 8.0, 6.0, 4.0])
    assert oef.classify_staircase(W) is not None
    from repro.core import jax_solve

    order, Ws = oef.classify_staircase(W)[1:]
    tau, X = jax_solve.solve_noncoop_fast_jax(W, m, _presorted=(order, Ws))
    alloc = backends.dispatch("oef-noncoop", W, m, backend="jax",
                              price_hint=np.ones(4))
    assert alloc.meta["instance_class"] == "monge"
    assert "search_iters" not in alloc.meta and "prices" not in alloc.meta
    assert alloc.meta["tau"] == tau
    np.testing.assert_array_equal(alloc.X, X)


def test_tpu_fleet_replay_stays_on_the_jax_tier():
    """32 self-profiled tenants on a TPU fleet whose v6e is one host, which
    fails and recovers: every decision is solved on the jax tier, none falls
    back, and the collective-heavy tenants keep their gang minimum of 2."""
    rng = np.random.default_rng(32)
    agent = ProfilingAgent(TPU_FLEET, error_pct=0.05, seed=32)
    job_types = [agent.profile(TPU_WORKLOADS[i])
                 for i in rng.integers(len(TPU_WORKLOADS), size=32)]
    cluster = ClusterSpec(types=tuple(d.name for d in TPU_FLEET), m=(16, 16, 8, 4))
    events = synthetic_trace(32, job_types=job_types, cluster=cluster,
                             duration_s=3600.0, seed=32)
    events += [Event(900.0, EventKind.HOST_FAIL, payload={"type": 3, "host": 0}),
               Event(1500.0, EventKind.HOST_RECOVER, payload={"type": 3, "host": 0})]
    events.sort(key=lambda e: e.time)
    sched = OnlineScheduler(cluster, "oef-noncoop", solver_backend="jax")
    report = sched.run(events)
    recs = sched.metrics.solves
    fresh = [r for r in recs if not r.reused]
    assert report.fallback_count == 0 and report.degraded_solves == 0
    assert set(report.solver_backends) == {"jax"}
    assert fresh and all(r.search_iters > 0 for r in fresh)
    assert all(r.search_iters == 0 for r in recs if r.reused)
    assert any(t.job_types and min(j.min_demand for j in t.job_types.values()) == 2
               for t in sched.tenants.values())
    dead = [r for r in recs if 900.0 <= r.time < 1500.0 and not r.reused]
    assert dead, "no decision while the v6e host was down"
