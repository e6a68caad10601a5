"""Tests for the cooperative primal–dual tier (core/jax_coop.py).

Parity is asserted against the scipy-LP ``solve_coop`` on the instance
families the tier is designed for — catalog-style populations (few distinct
speedup profiles, the online service's regime), degenerate ties, single
tenants, and small all-distinct instances — plus the envy kernel vs its jnp
reference, warm-started re-solves, the certified-or-fallback contract, the
batch API, and the scheduler integration on backend="jax".
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import backends, jax_coop, oef, properties  # noqa: E402
from repro.core.backends import BackendError  # noqa: E402
from repro.core.jax_solve import x64_scope  # noqa: E402
from repro.kernels.envy import envy_gaps, envy_gaps_ref  # noqa: E402

TOL = 1e-6


def catalog_instance(rng, n, g=5, k=3):
    """n tenants drawn from a g-profile catalog (the service's regime)."""
    cat = np.cumprod(1.0 + rng.uniform(0.05, 1.0, size=(g, k)), axis=1)
    cat /= cat[:, :1]
    W = cat[rng.integers(0, g, size=n)]
    m = rng.uniform(1.0, 4.0, size=k) * n / 4
    return W, m


def distinct_instance(rng, n, k=3):
    W = np.cumprod(1.0 + rng.uniform(0.05, 1.0, size=(n, k)), axis=1)
    W /= W[:, :1]
    m = rng.uniform(1.0, 4.0, size=k) * n / 4
    return W, m


def _envy_max(W, X):
    own = np.einsum("lk,lk->l", W, X)
    E = W @ X.T - own[:, None]
    np.fill_diagonal(E, 0.0)
    return float(E.max())


def _assert_parity(W, m, alloc):
    lp = oef.solve_coop(W, m)
    o_pd, o_lp = (W * alloc.X).sum(), (W * lp.X).sum()
    assert abs(o_pd - o_lp) <= TOL * max(abs(o_lp), 1.0)
    assert _envy_max(W, alloc.X) <= TOL
    assert np.all(alloc.X.sum(axis=0) <= m + 1e-9 * max(m.max(), 1.0))
    # both backends must pass the paper's EF + SI audits
    for X in (alloc.X, lp.X):
        rep = properties.property_report(W, X, m)
        assert rep["envy_free"] and rep["sharing_incentive"]


# ---------------------------------------------------------------------------
# Parity vs the LP
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
def test_catalog_parity(seed):
    rng = np.random.default_rng(100 + seed)
    W, m = catalog_instance(rng, int(rng.integers(8, 64)))
    alloc = jax_coop.solve_coop_pd(W, m)
    assert alloc.meta["policy"] == "oef-coop"
    lb, ub = alloc.meta["objective_bounds"]
    assert ub - lb <= 1e-6 * max(abs(lb), 1.0)  # the certificate itself
    _assert_parity(W, m, alloc)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_small_distinct_parity(n):
    rng = np.random.default_rng(n)
    W, m = distinct_instance(rng, n)
    try:
        alloc = jax_coop.solve_coop_pd(W, m)
    except BackendError:
        pytest.skip("instance did not certify within budget (documented; "
                    "dispatch falls back to the LP)")
    _assert_parity(W, m, alloc)


def test_degenerate_all_ties():
    # every tenant identical: dedup collapses to one group; the symmetric
    # optimum is an equal split of everything
    W = np.tile([[1.0, 2.0, 3.0]], (12, 1))
    m = np.array([4.0, 2.0, 6.0])
    alloc = jax_coop.solve_coop_pd(W, m)
    assert np.allclose(alloc.X, np.tile(m / 12, (12, 1)), atol=1e-8)
    _assert_parity(W, m, alloc)


def test_single_tenant_takes_all():
    W = np.array([[1.0, 2.0, 4.0]])
    m = np.array([3.0, 1.0, 2.0])
    alloc = jax_coop.solve_coop_pd(W, m)
    assert np.allclose(alloc.X, m[None, :])
    assert alloc.meta["pd_iters"] == 0


# ---------------------------------------------------------------------------
# Envy kernel vs reference
# ---------------------------------------------------------------------------


def test_envy_kernel_matches_ref_interpret():
    rng = np.random.default_rng(0)
    with x64_scope():
        for n, k in ((8, 3), (32, 4), (64, 2)):
            W = rng.uniform(0.5, 4.0, size=(n, k))
            X = rng.uniform(0.0, 2.0, size=(n, k))
            ref = np.asarray(envy_gaps_ref(W, X))
            ker = np.asarray(envy_gaps(W, X, interpret=True))
            assert np.allclose(ker, ref, atol=1e-12)


def test_envy_kernel_shape_mismatch_raises():
    with pytest.raises(ValueError, match="share"):
        envy_gaps(np.ones((4, 3)), np.ones((5, 3)))


def test_coop_pd_interpret_mode_matches():
    # the CI smoke rung: exercise the Pallas kernel via the interpreter
    rng = np.random.default_rng(42)
    W, m = catalog_instance(rng, 16)
    a_ref = jax_coop.solve_coop_pd(W, m)
    a_ker = jax_coop.solve_coop_pd(W, m, use_kernel=True, interpret=True)
    assert abs((W * a_ker.X).sum() - (W * a_ref.X).sum()) <= TOL
    assert _envy_max(W, a_ker.X) <= TOL


# ---------------------------------------------------------------------------
# Warm start, fallback, batch
# ---------------------------------------------------------------------------


def test_warm_start_reuses_state():
    rng = np.random.default_rng(1)
    W, m = catalog_instance(rng, 32)
    cold = jax_coop.solve_coop_pd(W, m)
    warm = jax_coop.solve_coop_pd(W, m * 1.02,
                                  prev_state=cold.meta["pd_state"])
    assert warm.meta["warm_started"] is True
    assert warm.meta["pd_iters"] <= cold.meta["pd_iters"]
    _assert_parity(W, m * 1.02, warm)


def test_warm_start_rejected_on_profile_change():
    rng = np.random.default_rng(2)
    W, m = catalog_instance(rng, 16)
    cold = jax_coop.solve_coop_pd(W, m)
    W2, m2 = catalog_instance(np.random.default_rng(3), 16)
    again = jax_coop.solve_coop_pd(W2, m2, prev_state=cold.meta["pd_state"])
    assert again.meta["warm_started"] is False


def test_budget_exhaustion_raises_backend_error():
    rng = np.random.default_rng(4)
    W, m = distinct_instance(rng, 24)  # hard family: many distinct rows
    with pytest.raises(BackendError, match="did not certify"):
        jax_coop.solve_coop_pd(W, m, max_iters=250, seg=250)


def test_dispatch_falls_back_to_lp_on_exhaustion():
    rng = np.random.default_rng(4)
    W, m = distinct_instance(rng, 24)
    alloc = backends.dispatch("oef-coop", W, m, backend="jax",
                              max_iters=250, seg=250)
    assert alloc.meta["backend"] == "lp"
    assert alloc.meta["fallback_from"] == "jax"
    assert "certify" in alloc.meta["fallback_reason"]
    assert _envy_max(W, alloc.X) <= TOL


def test_batch_matches_single():
    rng = np.random.default_rng(5)
    W, m = catalog_instance(rng, 8)
    Ws = np.stack([W, W[::-1]])
    Xs = jax_coop.solve_coop_batch(Ws, m)
    for b in range(2):
        single = jax_coop.solve_coop_pd(Ws[b], m)
        assert abs((Ws[b] * Xs[b]).sum() - (Ws[b] * single.X).sum()) <= TOL
        assert _envy_max(Ws[b], Xs[b]) <= TOL


def test_prewarm_compiles_buckets():
    sizes = jax_coop.prewarm(20, 3)
    assert sizes[-1] >= 20 and all(s & (s - 1) == 0 for s in sizes)


def test_prewarm_compiles_the_programs_solves_use():
    """A cold solve after prewarm adds no jit program: the PD segment it runs
    is the one prewarm compiled."""
    jax_coop.prewarm(6, 3)
    programs = jax_coop._pd_segment._cache_size()
    W, m = catalog_instance(np.random.default_rng(6), 40)
    alloc = jax_coop.solve_coop_pd(W, m)
    assert alloc.meta["pd_iters"] > 0
    assert jax_coop._pd_segment._cache_size() == programs


# ---------------------------------------------------------------------------
# Scheduler integration: oef-coop on backend="jax"
# ---------------------------------------------------------------------------


def test_scheduler_coop_jax_replay():
    from repro.service.scheduler import OnlineScheduler
    from repro.service.traces import default_cluster, default_job_types, synthetic_trace

    cluster = default_cluster("paper")
    events = synthetic_trace(
        3, job_types=default_job_types("paper"), cluster=cluster,
        duration_s=1800.0, mean_interarrival_s=300.0, mean_work_s=900.0,
        seed=0)
    sched = OnlineScheduler(cluster, "oef-coop", solver_backend="jax",
                            audit_every=1)
    report = sched.run(events, until=3600.0)
    assert report.n_solves > 0
    # every solve came off the registry chain: the PD tier or its LP fallback
    assert set(report.solver_backends) <= {"jax", "lp"}
    assert report.fallback_count <= report.n_solves
    for audit in report.fairness_audits:
        assert audit["envy_free"]
    # the telemetry JSON round-trips with the new fields
    assert '"solver_backends"' in report.to_json()
