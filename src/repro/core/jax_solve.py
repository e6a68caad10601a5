"""Batched, JIT-compiled water-filling solve tier for non-cooperative OEF.

The numpy greedy in :func:`repro.core.oef.solve_noncoop_fast` is exact but
sequential: a Python loop over users per bisection probe, ~100 ms at 1024
tenants. This module expresses the same exact water-filling in jax:

  - the per-tau feasibility check is the k-pass vectorized reduction of
    ``kernels/waterfill.py``: its jnp reference path on every platform,
    since the solve is float64 and Mosaic has none (the tiled Pallas kernel
    is opt-in via ``use_kernel=True`` in interpret mode);
  - the bisection is a fixed-iteration multisection: every step probes
    ``lanes`` equally spaced candidate taus at once and keeps the bracket
    between the last feasible and first infeasible lane, shrinking the
    bracket by ``lanes+1`` per step — fixed trip count, so the whole solve
    (probes + allocation recovery) is one jitted call with no host round
    trips;
  - scenario batches go through :func:`solve_noncoop_fast_batch`, a ``vmap``
    over the same core.

Instances are padded to power-of-two user-count buckets so the service's
fluctuating tenant population hits a handful of compiled programs instead of
one per population size; :func:`prewarm` compiles the buckets up front.

Float64 is required for ≤1e-9 parity with the numpy/LP solvers, so the
solve runs in float64. x64 is enabled *scoped*, via :func:`x64_scope`
around each entry point, never globally; hot-loop callers hold one scope
open across a whole replay, because entering it per solve costs jit
dispatch time. On TPU float64 runs in XLA's emulation;
the Pallas kernels cannot take it, so :func:`kernel_mode` refuses a compiled
float64 kernel outright instead of letting Mosaic fail inside a solve.

This tier only covers the (piecewise-)Monge staircase class of
``oef.classify_staircase`` — exactly where the greedy staircase is provably
optimal. Callers go through the backend registry
(``oef.solve_noncoop_fast(backend="jax")`` or
``backends.dispatch("oef-noncoop", ..., backend="jax")``), which falls back
to the scipy LP for anything else; the standalone entry points here raise
``ValueError`` instead so a silent wrong answer is impossible.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.waterfill import (
    waterfill_allocate,
    waterfill_masses,
    waterfill_masses_ref,
)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace

Array = np.ndarray

#: jit cache keys already compiled this process (prewarm registers its keys
#: too) — used to label the solve span "compile" vs "execute" and to count
#: recompiles per padding bucket without asking jax for its cache internals.
_COMPILED: set = set()

#: multisection lanes per step; bracket shrinks by LANES+1 each iteration.
LANES = 8
#: fixed trip count: 9**14 ~ 2e13 bracket reduction. The cold bracket starts
#: at the tight capacity bound sum_j m_j max_u w_uj / n (a true upper bound
#: on tau: n*tau = sum of user throughputs <= each type's capacity at its
#: best user's speed), so tau lands ~1e-11 absolute from the optimum — inside
#: the 1e-9 parity budget with two decades of margin even after the O(n)
#: error propagation into the recovered allocation. The per-step cumsum scan
#: is the wall-clock driver, so trips are kept minimal.
ITERS = 14
#: smallest padding bucket (power-of-two buckets above).
MIN_PAD = 8
#: persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path in the checkout (git-ignored), so later runs of the same
#: checkout find the padding-bucket programs again.
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Call before the first compile. ``JAX_COMPILATION_CACHE_DIR``, when set,
    is JAX's own setting and is left alone; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`. The small bucket programs compile faster
    than JAX's default one-second floor for caching, so the floor is
    dropped and every program is cached.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def x64_scope():
    """Context that guarantees float64 tracing for the enclosed jax calls.

    Entering ``jax.enable_x64(True)`` costs ~0.75 ms per call on the CPU
    (the config flip knocks jit dispatch off the C++ fast path), so hot loops —
    the online scheduler's replay, the latency benchmark — hold one scope
    open across many solves and this helper turns the per-solve entry into
    a no-op when x64 is already on.
    """
    if jax.config.jax_enable_x64:
        return contextlib.nullcontext()
    return jax.enable_x64(True)


def kernel_mode(use_kernel: bool, interpret: Optional[bool],
                dtype) -> Tuple[bool, bool]:
    """Resolve a solve's ``(use_kernel, interpret)`` jit flags.

    The jnp reference path is the default on every platform. A compiled
    (non-interpret) kernel is refused for float64 operands: Mosaic has no
    float64, and the failure would otherwise surface inside the solve, where
    ``dispatch``'s failsafe turns it into an LP fallback. ``interpret`` only
    affects the kernel, so it is pinned off on the jnp path and the jit key
    matches what ``prewarm`` compiled.
    """
    if not use_kernel:
        return False, False
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and np.dtype(dtype) == np.float64:
        raise ValueError(
            f"use_kernel=True with {np.dtype(dtype)} operands needs "
            "interpret=True: the Pallas TPU compiler has no float64; leave "
            "use_kernel off to run the jnp reference path")
    return True, bool(interpret)


def bucket(n: int) -> int:
    """Padded user count: next power of two >= n (min MIN_PAD)."""
    if n <= MIN_PAD:
        return MIN_PAD
    return 1 << (n - 1).bit_length()


def _feasible(masses_fn, taus, Wf, m, mask, n_active):
    mass = masses_fn(taus, Wf, m, mask)
    # The mass decays linearly in (tau - tau*) above the optimum; the
    # tolerance only needs to absorb the ~1e-13-relative cumsum noise, and
    # shifts the recovered tau by tol/n — far inside the 1e-9 parity budget.
    return mass <= 1e-12 * (1.0 + n_active * taus)


@functools.partial(
    jax.jit,
    static_argnames=("lanes", "iters", "use_hint", "use_kernel", "interpret"),
)
def _solve_padded(Wf, m, mask, tau_hint, *, lanes: int = LANES, iters: int = ITERS,
                  use_hint: bool = False, use_kernel: bool = False,
                  interpret: bool = False):
    """Jitted core: multisection + allocation recovery on a padded instance.

    Wf is (n_pad, k) sorted fastest user first with padding rows masked out;
    returns (tau, X) with X in the same (padded, reversed) row order.
    """
    masses_fn = (
        functools.partial(waterfill_masses, interpret=interpret)
        if use_kernel else waterfill_masses_ref
    )
    n_active = mask.sum()
    # Tight bracket: n*tau <= sum_j m_j max_u w_uj (every device at most at
    # its best active user's speed) — an n-times smaller starting bracket
    # than max(W)*sum(m), which is what lets ITERS stay at 14.
    hi_cap = jnp.max(Wf * mask[:, None], axis=0) @ m / n_active + 1.0
    lo = jnp.zeros((), Wf.dtype)
    hi = hi_cap
    if use_hint:
        # One probe decides which side of the hint the bracket keeps — the
        # fixed-trip multisection below stays correct for any hint quality.
        h = jnp.clip(tau_hint, 0.0, hi_cap)
        ok = _feasible(masses_fn, h[None], Wf, m, mask, n_active)[0]
        lo = jnp.where(ok, h, lo)
        hi = jnp.where(ok, hi, h)
    frac = jnp.arange(1, lanes + 1, dtype=Wf.dtype) / (lanes + 1.0)

    def step(_, bracket):
        lo, hi = bracket
        taus = lo + (hi - lo) * frac
        feas = _feasible(masses_fn, taus, Wf, m, mask, n_active)
        i = feas.sum()  # feasibility is monotone: lanes form a true-prefix
        new_lo = jnp.where(i > 0, taus[jnp.maximum(i - 1, 0)], lo)
        new_hi = jnp.where(i < lanes, taus[jnp.minimum(i, lanes - 1)], hi)
        return new_lo, new_hi

    lo, hi = lax.fori_loop(0, iters, step, (lo, hi))
    return lo, waterfill_allocate(lo, Wf, m, mask)


def _pad_sorted(Ws: Array, k: int) -> Tuple[Array, Array]:
    """Pad a slowest-first sorted matrix to its bucket; fastest user first."""
    n = Ws.shape[0]
    n_pad = bucket(n)
    Wf = np.ones((n_pad, k), dtype=np.float64)
    Wf[:n] = Ws[::-1]  # fastest user first, as the greedy consumes the tape
    mask = np.zeros(n_pad, dtype=np.float64)
    mask[:n] = 1.0
    return Wf, mask


def _prepare(
    W: Array, m: Array, presorted: Optional[Tuple[Array, Array]] = None
) -> Tuple[Array, Array, Array, Array]:
    """Validate + sort + pad one instance; returns (order, Wf, m64, mask).

    ``presorted`` is the (order, Ws) pair a caller that already classified
    the instance (``oef.solve_noncoop_waterfill_jax``) passes down so the
    argsort and class checks are not repeated on the hot path.
    """
    from .oef import classify_staircase  # deferred: oef lazily imports us

    W = np.asarray(W, dtype=np.float64)
    m = np.asarray(m, dtype=np.float64)
    if W.ndim != 2 or W.shape[0] < 1:
        raise ValueError(f"need a (n>=1, k) speedup matrix, got {W.shape}")
    if presorted is not None:
        order, Ws = presorted
    else:
        cls = classify_staircase(W)
        if cls is None:
            raise ValueError(
                "instance is neither consistently ordered (Monge) nor "
                "piecewise-Monge; the closed-form water-filling does not "
                "apply — solve via the LP instead (the oef-noncoop backend "
                "chain handles this fallback automatically)")
        _, order, Ws = cls
    Wf, mask = _pad_sorted(Ws, W.shape[1])
    return order, Wf, m, mask


def solve_noncoop_fast_jax(
    W: Array,
    m: Array,
    *,
    tau_hint: Optional[float] = None,
    lanes: int = LANES,
    iters: int = ITERS,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    _presorted: Optional[Tuple[Array, Array]] = None,
) -> Tuple[float, Array]:
    """Exact water-filling solve of one instance on the jax tier.

    Returns ``(tau, X)`` in the original row order. Raises ``ValueError``
    for instances outside the consistently-ordered class (callers that want
    the automatic LP fallback use ``oef.solve_noncoop_fast(backend="jax")``),
    and for ``use_kernel=True`` outside interpret mode (see
    :func:`kernel_mode`).
    """
    with obs_trace.span("prepare", "jax", tier="noncoop"):
        order, Wf, m, mask = _prepare(W, m, _presorted)
    n, k = np.asarray(W).shape
    use_kernel, interpret = kernel_mode(use_kernel, interpret, Wf.dtype)
    hi_cap = float(np.max(W) * m.sum()) + 1.0
    use_hint = tau_hint is not None and 0.0 < float(tau_hint) < hi_cap
    hint = float(tau_hint) if use_hint else -1.0
    key = (Wf.shape, lanes, iters, use_hint, use_kernel, interpret)
    fresh = key not in _COMPILED
    if fresh:
        _COMPILED.add(key)
        reg = obs_metrics.get_metrics()
        if reg is not None:
            reg.counter(f"jax.recompiles.noncoop.b{Wf.shape[0]}").inc()
    with x64_scope():
        with obs_trace.span("compile" if fresh else "execute", "jax",
                            tier="noncoop", bucket=Wf.shape[0]):
            # numpy operands go straight into the jitted call: pjit's C++
            # dispatch does the host->device transfer far cheaper than an
            # explicit jnp.asarray per operand (~1 ms/solve at 1024 tenants).
            tau, Xf = _solve_padded(
                Wf, m, mask, np.float64(hint),
                lanes=lanes, iters=iters, use_hint=use_hint,
                use_kernel=use_kernel, interpret=interpret)
            tau = float(tau)
            Xf = np.asarray(Xf)
    X = np.zeros((n, k), dtype=np.float64)
    X[order] = Xf[:n][::-1]
    return tau, X


def solve_noncoop_fast_batch(
    Ws: Array, ms: Array, *, lanes: int = LANES, iters: int = ITERS
) -> Tuple[Array, Array]:
    """Batched solve: ``vmap`` over (B, n, k) instances sharing a user count.

    ``ms`` is (B, k) or a single (k,) capacity broadcast to the batch.
    Every instance must be consistently ordered (ValueError otherwise).
    Returns ``(taus (B,), Xs (B, n, k))`` in each instance's original row
    order. Scenario sweeps (capacity what-ifs, profiling-noise ensembles)
    amortize one compile across the whole batch.
    """
    Ws = np.asarray(Ws, dtype=np.float64)
    if Ws.ndim != 3:
        raise ValueError(f"need (B, n, k) stacked instances, got {Ws.shape}")
    B, n, k = Ws.shape
    ms = np.asarray(ms, dtype=np.float64)
    if ms.ndim == 1:
        ms = np.broadcast_to(ms, (B, k))
    orders = []
    Wfs = np.ones((B, bucket(n), k), dtype=np.float64)
    masks = np.zeros((B, bucket(n)), dtype=np.float64)
    for b in range(B):
        order, Wf, _, mask = _prepare(Ws[b], ms[b])
        orders.append(order)
        Wfs[b], masks[b] = Wf, mask
    core = functools.partial(_solve_padded, lanes=lanes, iters=iters,
                             use_hint=False, use_kernel=False, interpret=False)
    with x64_scope():
        taus, Xfs = jax.vmap(
            lambda Wf, m, mask: core(Wf, m, mask, jnp.asarray(-1.0, jnp.float64))
        )(jnp.asarray(Wfs), jnp.asarray(ms), jnp.asarray(masks))
        taus = np.asarray(taus)
        Xfs = np.asarray(Xfs)
    Xs = np.zeros((B, n, k), dtype=np.float64)
    for b, order in enumerate(orders):
        Xs[b][order] = Xfs[b, :n][::-1]
    return taus, Xs


def prewarm(n_max: int, k: int, *, lanes: int = LANES, iters: int = ITERS) -> List[int]:
    """Compile the padded-bucket programs up to ``bucket(n_max)``.

    The online service's tenant population drifts through many sizes; calling
    this before the replay keeps jit compiles out of the measured re-solve
    latency. Both the cold and warm-started (``tau_hint``) variants are
    compiled per bucket. Returns the bucket sizes compiled.
    """
    sizes = []
    s = MIN_PAD
    while s < bucket(n_max):
        sizes.append(s)
        s *= 2
    sizes.append(bucket(n_max))
    m = np.full(k, 2.0)
    with obs_trace.span("prewarm", "jax", tier="noncoop", buckets=len(sizes)):
        with x64_scope():
            for n_pad in sizes:
                args = (np.ones((n_pad, k)), m, np.ones(n_pad))
                for use_hint, hint in ((False, -1.0), (True, 0.5)):
                    tau, _ = _solve_padded(
                        *args, np.float64(hint), lanes=lanes,
                        iters=iters, use_hint=use_hint, use_kernel=False,
                        interpret=False)
                    tau.block_until_ready()
                    _COMPILED.add(((n_pad, k), lanes, iters, use_hint,
                                   False, False))
    return sizes
