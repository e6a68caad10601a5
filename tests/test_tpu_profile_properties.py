"""Fairness properties on self-profiled TPU rows.

Tenants of the TPU fleet profile their own jobs (OEF §4.1): each row is a
``TPU_WORKLOADS`` roofline profile with 5% measurement noise, so every row
is distinct and memory-bound jobs rank the generations differently from
compute-bound ones. No such instance is in the staircase class. On these
rows, at 8, 32 and 128 tenants:

  - cooperative OEF is envy-free and gives sharing incentive (Thm 5.1);
  - non-cooperative OEF, on the jax tier's general price search that the
    TPU-fleet service runs, equalizes throughput and leaves nothing for a
    Pareto-improving LP to find (Thms 5.3-5.4);
  - efficiency-only's total throughput bounds every other program's;
  - Gavel and Gandiva_fair give sharing incentive within capacity.

LP parity of the price search is ``test_jax_general.py``'s; this file holds
the properties.
"""
import numpy as np
import pytest

from repro.core import backends, oef, properties
from repro.core.baselines import solve_gandiva_fair, solve_gavel, solve_maxmin
from repro.core.profiler import ProfilingAgent
from repro.core.types import TPU_FLEET
from repro.service.traces import TPU_WORKLOADS, default_cluster

SIZES = [8, 32, 128]
TOL = 1e-6


def self_profiled(n):
    """``n`` tenants cycling over the TPU catalog, each profiling its own
    job with 5% error, and the TPU fleet's capacity."""
    agent = ProfilingAgent(TPU_FLEET, error_pct=0.05, seed=n)
    W = np.array([agent.profile(TPU_WORKLOADS[i % len(TPU_WORKLOADS)]).speedup
                  for i in range(n)])
    assert oef.classify_staircase(W) is None  # off the staircase class
    return W, default_cluster("tpu").m_vec


def assert_within_capacity(X, m):
    assert np.all(X >= -1e-9)
    assert np.all(X.sum(axis=0) <= m + 1e-9)


@pytest.mark.parametrize("n", SIZES)
def test_coop_is_envy_free_and_sharing_incentive(n):
    W, m = self_profiled(n)
    X = oef.solve_coop(W, m).X
    assert_within_capacity(X, m)
    assert properties.is_envy_free(W, X, tol=TOL), np.max(properties.envy_matrix(W, X))
    assert properties.is_sharing_incentive(W, X, m, tol=TOL)


@pytest.mark.parametrize("n", SIZES)
def test_noncoop_equal_throughput_and_pareto_efficient(n):
    W, m = self_profiled(n)
    alloc = backends.dispatch("oef-noncoop", W, m, backend="jax")
    assert alloc.meta["backend"] == "jax" and "fallback_reason" not in alloc.meta
    X = alloc.X
    assert_within_capacity(X, m)
    t = np.einsum("lk,lk->l", W, X)
    assert np.ptp(t) <= 1e-9 * t.mean()
    # with every speedup positive, an equal-throughput optimum is Pareto
    # efficient outright: no allocation helps one tenant and hurts none
    assert properties.pareto_improvement_value(W, X, m) <= 1e-5


@pytest.mark.parametrize("n", SIZES)
def test_efficiency_only_bounds_every_program(n):
    W, m = self_profiled(n)
    best = properties.total_efficiency(W, oef.solve_efficiency_only(W, m).X)
    others = {
        "oef-coop": oef.solve_coop(W, m).X,
        "oef-noncoop": oef.solve_noncoop(W, m).X,
        "max-min": solve_maxmin(W, m).X,
        "gavel": solve_gavel(W, m).X,
        "gandiva-fair": solve_gandiva_fair(W, m).X,
    }
    for name, X in others.items():
        assert_within_capacity(X, m)
        assert properties.total_efficiency(W, X) <= best * (1 + TOL), name


@pytest.mark.parametrize("n", SIZES)
def test_gavel_and_gandiva_fair_give_sharing_incentive(n):
    W, m = self_profiled(n)
    for solve, tol in ((solve_gavel, 1e-4), (solve_gandiva_fair, TOL)):
        X = solve(W, m).X
        assert_within_capacity(X, m)
        assert properties.is_sharing_incentive(W, X, m, tol=tol), solve.__name__
