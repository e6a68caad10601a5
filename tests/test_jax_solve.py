"""jax solve tier vs the numpy/LP solvers: exact-parity tests.

The jax water-filling tier (``core.jax_solve`` + the Pallas reduction in
``kernels.waterfill``) must be *numerically interchangeable* with
``oef.solve_noncoop_fast(backend="numpy")`` — same tau, same allocation, to
<= 1e-9 — across random consistently-ordered instances, the warm-start
``tau_hint`` path, padded sizes, and the batched vmap API. Off the class
the ``backend="jax"`` knob answers with the general price search
(``tests/test_jax_general.py``), while the standalone water-filling entry
points refuse such instances.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import jax_coop, jax_solve, oef
from repro.core.jax_solve import bucket, solve_noncoop_fast_batch, solve_noncoop_fast_jax
from repro.kernels.waterfill import waterfill_masses, waterfill_masses_ref

PARITY_TOL = 1e-9


def monge_instance(rng, n=None, k=None):
    """Random consistently-ordered instance: W[l, j] = a_l ** c_j with both
    exponents ascending (same construction as test_oef_properties)."""
    n = n if n is not None else int(rng.integers(1, 24))
    k = k if k is not None else int(rng.integers(2, 5))
    a = np.cumsum(rng.uniform(0.05, 0.8, size=n)) + 1.0
    c = np.cumsum(rng.uniform(0.05, 0.6, size=k))
    c = c - c[0]
    W = np.power(a[:, None], c[None, :])
    m = rng.integers(1, 9, size=k).astype(float)
    return W, m


def assert_parity(W, m, *, tau_hint=None):
    ref = oef.solve_noncoop_fast(W, m, backend="numpy")
    got = oef.solve_noncoop_fast(W, m, backend="jax", tau_hint=tau_hint)
    assert got.meta["backend"] == "jax"
    assert got.meta["fast_path"] is True
    assert abs(got.meta["tau"] - ref.meta["tau"]) <= PARITY_TOL
    np.testing.assert_allclose(got.X, ref.X, atol=PARITY_TOL, rtol=0)


# ---------------------------------------------------------------------------
# parity: random instances, seeded sweep (runs even without hypothesis)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(8))
def test_jax_matches_numpy_random_instances(seed):
    rng = np.random.default_rng(seed)
    for _ in range(12):
        W, m = monge_instance(rng)
        assert_parity(W, m)


def test_jax_matches_numpy_across_padding_buckets():
    """Sizes straddling every padding-bucket boundary up to 64."""
    rng = np.random.default_rng(7)
    for n in (1, 2, 7, 8, 9, 15, 16, 17, 31, 32, 33, 63, 64):
        W, m = monge_instance(rng, n=n, k=3)
        assert_parity(W, m)


def test_jax_matches_numpy_fractional_capacity():
    rng = np.random.default_rng(11)
    W, _ = monge_instance(rng, n=9, k=3)
    m = np.array([2.5, 0.75, 4.25])
    assert_parity(W, m)


@pytest.mark.parametrize("seed", range(4))
def test_warm_start_hint_parity(seed):
    """tau_hint must change latency only, never the answer — good hints,
    terrible hints, and out-of-range hints all converge identically."""
    rng = np.random.default_rng(100 + seed)
    W, m = monge_instance(rng)
    tau_ref = oef.solve_noncoop_fast(W, m, backend="numpy").meta["tau"]
    for hint in (tau_ref, tau_ref * 0.5, tau_ref * 2.0, 1e-6, 1e9, -3.0):
        assert_parity(W, m, tau_hint=hint)


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=40, deadline=None)
def test_jax_matches_numpy_property(seed):
    rng = np.random.default_rng(seed)
    W, m = monge_instance(rng)
    assert_parity(W, m)
    assert_parity(W, m, tau_hint=float(rng.uniform(0.0, 5.0)))


# ---------------------------------------------------------------------------
# LP-fallback boundary
# ---------------------------------------------------------------------------
def test_backend_jax_falls_back_to_lp_on_unordered():
    """Off the staircase class the jax tier no longer falls back: it answers
    with its general search, and the numpy tier still falls back to the LP."""
    W = np.array([[1.0, 3.0], [2.0, 1.0]])  # rows order differently per type
    m = np.array([2.0, 2.0])
    got = oef.solve_noncoop_fast(W, m, backend="jax")
    ref = oef.solve_noncoop_fast(W, m, backend="numpy")
    assert ref.meta["backend"] == "lp"
    assert got.meta["backend"] == "jax"
    assert got.meta["instance_class"] == "general"
    assert "fallback_reason" not in got.meta
    assert abs(got.meta["tau"] - ref.meta["tau"]) <= PARITY_TOL


def test_jax_entry_point_rejects_unordered():
    """The standalone tier raises instead of silently mis-solving."""
    W = np.array([[1.0, 3.0], [2.0, 1.0]])
    with pytest.raises(ValueError, match="consistently ordered"):
        solve_noncoop_fast_jax(W, np.array([2.0, 2.0]))


def test_backend_validation():
    W = np.array([[1.0, 2.0]])
    with pytest.raises(ValueError, match="backend"):
        oef.solve_noncoop_fast(W, np.array([1.0, 1.0]), backend="fortran")


# ---------------------------------------------------------------------------
# Pallas kernel vs jnp reference path
# ---------------------------------------------------------------------------
def test_pallas_kernel_matches_reference():
    rng = np.random.default_rng(3)
    with jax_solve.x64_scope():
        for n, k in ((8, 2), (16, 3), (64, 4), (256, 3)):
            W, m = monge_instance(rng, n=n, k=k)
            _, Wf, m64, mask = jax_solve._prepare(W, m)
            hi = float(W.max() * m.sum()) + 1.0
            taus = jnp.linspace(0.0, hi, 16, dtype=jnp.float64)
            args = (taus, jnp.asarray(Wf), jnp.asarray(m64), jnp.asarray(mask))
            got = waterfill_masses(*args, interpret=True)
            ref = waterfill_masses_ref(*args)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-12, rtol=1e-12)


def test_full_solve_through_kernel_matches_numpy():
    rng = np.random.default_rng(5)
    W, m = monge_instance(rng, n=12, k=3)
    ref = oef.solve_noncoop_fast(W, m, backend="numpy")
    tau, X = solve_noncoop_fast_jax(W, m, use_kernel=True, interpret=True)
    assert abs(tau - ref.meta["tau"]) <= PARITY_TOL
    np.testing.assert_allclose(X, ref.X, atol=PARITY_TOL, rtol=0)


# ---------------------------------------------------------------------------
# batched API
# ---------------------------------------------------------------------------
def test_batch_matches_single_solves():
    rng = np.random.default_rng(17)
    B, n, k = 5, 10, 3
    Ws = np.stack([monge_instance(rng, n=n, k=k)[0] for _ in range(B)])
    ms = np.stack([np.asarray(monge_instance(rng, n=1, k=k)[1]) for _ in range(B)])
    taus, Xs = solve_noncoop_fast_batch(Ws, ms)
    assert taus.shape == (B,) and Xs.shape == (B, n, k)
    for b in range(B):
        ref = oef.solve_noncoop_fast(Ws[b], ms[b], backend="numpy")
        assert abs(taus[b] - ref.meta["tau"]) <= PARITY_TOL
        np.testing.assert_allclose(Xs[b], ref.X, atol=PARITY_TOL, rtol=0)


def test_batch_broadcasts_shared_capacity():
    rng = np.random.default_rng(19)
    W, m = monge_instance(rng, n=6, k=3)
    taus, Xs = solve_noncoop_fast_batch(np.stack([W, W]), m)
    assert abs(taus[0] - taus[1]) == 0.0
    np.testing.assert_allclose(Xs[0], Xs[1], atol=0, rtol=0)


# ---------------------------------------------------------------------------
# integration: incremental hook and the online scheduler
# ---------------------------------------------------------------------------
def test_solve_incremental_backend_knob():
    rng = np.random.default_rng(23)
    W, m = monge_instance(rng, n=8, k=3)
    first = oef.solve_incremental(W, m, policy="oef-noncoop", backend="jax")
    assert first.meta["backend"] == "jax"
    # warm re-solve on a perturbed instance goes through the tau_hint path
    W2 = W * 1.01
    second = oef.solve_incremental(W2, m, policy="oef-noncoop", prev=first,
                                   backend="jax")
    ref = oef.solve_noncoop_fast(W2, m, backend="numpy")
    assert second.meta["warm_started"] is True
    assert abs(second.meta["tau"] - ref.meta["tau"]) <= PARITY_TOL
    # unchanged instance short-circuits to reuse regardless of backend
    third = oef.solve_incremental(W2, m, policy="oef-noncoop", prev=second,
                                  backend="jax")
    assert third.meta.get("reused") is True


def test_scheduler_replay_identical_across_backends():
    """A full replay must produce event-for-event identical reports: the jax
    tier swaps the arithmetic, never the decisions."""
    from repro.core.types import ClusterSpec
    from repro.service import OnlineScheduler, synthetic_trace
    from repro.service.traces import default_job_types

    cluster = ClusterSpec(types=("rtx3070", "rtx3080", "rtx3090"), m=(8, 8, 8))
    events = synthetic_trace(6, job_types=default_job_types("paper"),
                             cluster=cluster, duration_s=1800.0,
                             mean_interarrival_s=300.0, mean_work_s=900.0,
                             seed=4)
    reports = {}
    for backend in ("numpy", "jax"):
        sched = OnlineScheduler(cluster, "oef-noncoop",
                                min_resolve_interval_s=30.0,
                                solver_backend=backend)
        reports[backend] = sched.run(events, until=3600.0)
    a, b = reports["numpy"], reports["jax"]
    assert a.n_solves == b.n_solves
    assert a.jobs_finished == b.jobs_finished
    assert a.n_events == b.n_events
    assert abs(a.mean_jct_s - b.mean_jct_s) <= 1e-6 * max(a.mean_jct_s, 1.0)
    for name in a.tenant_throughput:
        assert abs(a.tenant_throughput[name] - b.tenant_throughput[name]) <= 1e-6


def test_scheduler_rejects_unknown_backend():
    from repro.core.types import ClusterSpec
    from repro.service import OnlineScheduler

    cluster = ClusterSpec(types=("a",), m=(4,))
    with pytest.raises(ValueError, match="backend"):
        OnlineScheduler(cluster, "oef-noncoop", solver_backend="cuda")


# ---------------------------------------------------------------------------
# plumbing invariants
# ---------------------------------------------------------------------------
def test_bucket_boundaries():
    assert [bucket(n) for n in (1, 8, 9, 16, 17, 1000, 1024)] == \
        [8, 8, 16, 16, 32, 1024, 1024]


def test_x64_scope_does_not_leak():
    """The solver needs float64 internally but must not flip the process-wide
    float32 default for the rest of the process."""
    rng = np.random.default_rng(29)
    W, m = monge_instance(rng, n=4, k=2)
    solve_noncoop_fast_jax(W, m)
    assert jnp.asarray(1.5).dtype == jnp.float32
    assert not jax.config.jax_enable_x64


def test_prewarm_covers_buckets():
    sizes = jax_solve.prewarm(20, 2)
    assert sizes == [8, 16, 32]


def test_prewarm_compiles_the_programs_solves_use():
    """Cold and warm-started solves after prewarm add no jit program, so the
    compile/execute span labels and the recompile counters are truthful."""
    jax_solve.prewarm(20, 3)
    programs = jax_solve._solve_padded._cache_size()
    rng = np.random.default_rng(37)
    W, m = monge_instance(rng, n=12, k=3)
    tau, _ = solve_noncoop_fast_jax(W, m)
    solve_noncoop_fast_jax(W * 1.01, m, tau_hint=tau)
    assert jax_solve._solve_padded._cache_size() == programs


@pytest.mark.parametrize("tier", ["noncoop", "coop"])
def test_compiled_kernel_refuses_float64(tier):
    """The Pallas TPU compiler has no float64: asking for the compiled kernel
    with the solves' float64 operands fails up front, naming the dtype,
    rather than inside the solve where dispatch's failsafe would turn it
    into a silent LP fallback."""
    W, m = monge_instance(np.random.default_rng(31), n=8, k=3)
    solve = (solve_noncoop_fast_jax if tier == "noncoop"
             else jax_coop.solve_coop_pd)
    with pytest.raises(ValueError, match="float64"):
        solve(W, m, use_kernel=True, interpret=False)


_CACHE_PROBE = """
import jax
from repro.core import jax_solve
print(jax_solve.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(jax.config.jax_persistent_cache_min_compile_time_secs)
if {compile}:
    jax_solve.prewarm(8, 2)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins when set, and the bucket programs land
    there; otherwise the cache sits at the fixed in-checkout path."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = _CACHE_PROBE.format(compile=from_env)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    path, config_dir, min_secs = r.stdout.split()[:3]
    want = str(tmp_path) if from_env else jax_solve.CHECKOUT_CACHE_DIR
    assert path == config_dir == want
    assert float(min_secs) == 0.0
    if from_env:
        assert any("_solve_padded" in f for f in os.listdir(tmp_path))
