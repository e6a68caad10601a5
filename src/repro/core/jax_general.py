"""Device solve of non-cooperative OEF (Eq. 9) off the staircase class.

Water-filling (:mod:`repro.core.jax_solve`) is exact only on the
(piecewise-)Monge class of ``oef.classify_staircase``. A fleet whose
generations rank jobs differently — a TPU fleet where memory-bound jobs
prefer v5p and compute-bound ones v6e — leaves it. This module solves the
general Eq. 9 LP through its dual, which has only ``k`` unknowns:

    t* = min_{p >= 0}  sum_j m_j p_j / sum_l min_j (p_j / w_lj)

where ``p`` are the capacity prices and each tenant buys its throughput at
its cheapest type. Two phases:

  - **search (device)**: a log-barrier path on the dual, maximizing
    ``sum_l c_l v_l + mu sum_lj c_l log(p_j / w_lj - v_l)`` over the
    normalized prices ``m . p = sum_j m_j``. Every tenant's ``v_l`` is
    centred in closed-form Newton steps, so each outer step is a Newton step
    in ``p`` alone: the per-tenant blocks of the Hessian are diagonal and
    reduce to a ``(k+1) x (k+1)`` system. A batched line search over
    ``LINE_STEPS`` halvings picks the longest step whose directional
    derivative is still positive, and ``mu`` shrinks by ``THETA`` once the
    Newton decrement is small. Each jitted segment runs ``SEG_ITERS`` steps;
  - **crossover (host, float64)**: from the prices, each tenant takes its
    cheapest type; the tenants closest to a tie join the types into one tree
    (at most ``k - 1`` of them split), the small linear system of equal
    throughput and full capacity gives ``t`` and the split amounts, and the
    prices that make the split tenants indifferent give the dual bound
    above. Where distinct rows tie exactly at those prices, more than the
    tree can split, the tied rows share their types by non-negative least
    squares instead. The answer is accepted only when the primal is
    non-negative and the bound is within ``CERT_TOL`` of it; otherwise the
    search runs another segment. A search that does not certify within ``max_iters`` raises
    :class:`~repro.core.backends.BackendError` (the registry's LP fallback).

Identical rows are one row with a count (tenants drawn from a profile
catalog share rows; the LP is symmetric in them). The instance is padded to
the power-of-two bucket of its tenant count; padding rows have count zero
and drop out of every sum. The search warm-starts from the previous
answer's prices, which stay meaningful as tenants join and leave.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from . import backends
from .jax_solve import bucket, x64_scope

Array = np.ndarray

#: search-segment jit cache keys compiled this process (see
#: ``jax_solve._COMPILED``).
_COMPILED: set = set()

#: Newton steps per jitted search segment (one crossover attempt each).
SEG_ITERS = 16
#: search budget before the LP fallback fires.
MAX_ITERS = 128
#: closed-form Newton steps that centre every tenant's ``v`` (monotone from
#: the feasible side, quadratic once close).
CENTER_ITERS = 16
#: line-search candidates: steps 1, 1/2, ..., 2**-(LINE_STEPS - 1).
LINE_STEPS = 12
#: barrier weight of a cold start (prices normalized to mean ~1) and of a
#: warm start from the previous answer's prices.
MU_COLD = 0.1
MU_WARM = 1e-4
#: iterations a warm start gets before the search restarts cold.
WARM_ITERS = 2 * SEG_ITERS
#: ``mu`` shrink factor once the Newton decrement drops under DECREMENT.
THETA = 0.1
DECREMENT = 0.25
#: smallest barrier weight: float64 rounding stalls the path below it.
MU_MIN = 1e-13
#: certificate tolerance: relative gap between the dual bound and ``t``.
CERT_TOL = 1e-10


# ---------------------------------------------------------------------------
# device phase: barrier path on the dual prices
# ---------------------------------------------------------------------------


def _center(q, act, mu):
    """Centred ``v`` of each row for prices ``q = p / w``: the root of
    ``1 = mu * sum_j 1 / (q_j - v)`` below ``min_j q_j``, by Newton steps
    from the feasible side (monotone, since the residual is concave)."""
    big = jnp.where(act > 0, q, jnp.inf)
    v = big.min(axis=-1) - 0.5 * mu

    def step(_, v):
        inv = act / jnp.where(act > 0, q - v[..., None], 1.0)
        res = 1.0 - mu * inv.sum(axis=-1)
        slope = mu * (inv * inv).sum(axis=-1)
        return v + res / slope

    return lax.fori_loop(0, CENTER_ITERS, step, v)


def _spd_solve(S, b):
    """``S^-1 b`` for a small symmetric positive definite ``S`` by unrolled
    Gaussian elimination (``k`` is static; no pivoting needed)."""
    k = S.shape[0]
    S = [[S[i, j] for j in range(k)] for i in range(k)]
    b = [b[i] for i in range(k)]
    for c in range(k):
        for r in range(c + 1, k):
            f = S[r][c] / S[c][c]
            S[r] = [S[r][j] - f * S[c][j] for j in range(k)]
            b[r] = b[r] - f * b[c]
    x = [None] * k
    for r in range(k - 1, -1, -1):
        acc = b[r]
        for j in range(r + 1, k):
            acc = acc - S[r][j] * x[j]
        x[r] = acc / S[r][r]
    return jnp.stack(x)


@jax.jit
def _search_segment(A, cnt, m, p, mu):
    """``SEG_ITERS`` barrier Newton steps on the prices ``p`` from weight
    ``mu``.

    ``A`` is ``1 / W`` padded to the bucket (padding rows have ``cnt = 0``),
    ``m`` the capacities (a type with ``m_j = 0`` is left out and its price
    stays 0). Returns the new ``(p, mu)``.
    """
    act = (m > 0).astype(A.dtype)
    total = (m * act).sum()
    alphas = 0.5 ** jnp.arange(LINE_STEPS, dtype=A.dtype)

    def step(_, state):
        p, mu = state
        q = A * p
        v = _center(q, act, mu)
        inv = act / jnp.where(act > 0, q - v[:, None], 1.0)
        d = mu * cnt[:, None] * inv * inv
        D = jnp.where(cnt > 0, d.sum(axis=1), 1.0)
        da = d * A
        grad = mu * (cnt[:, None] * A * inv).sum(axis=0)
        # Schur complement of the per-row blocks: the reduced Hessian in p
        S = jnp.diag((da * A).sum(axis=0) + (1.0 - act)) - (da / D[:, None]).T @ da
        # Newton step on {m . p = total}: dp = S^-1 (grad - nu m), m . dp = 0
        sol = _spd_solve(S, jnp.stack([grad * act, m * act], axis=1))
        nu = (m * sol[:, 0]).sum() / (m * sol[:, 1]).sum()
        dp = (sol[:, 0] - nu * sol[:, 1]) * act
        decrement = jnp.sqrt(jnp.maximum((grad * dp).sum(), 0.0) / mu)
        # batched line search: the concave objective rises up to the
        # longest step whose directional derivative is still positive
        P = p[None, :] + alphas[:, None] * dp[None, :]
        ok = jnp.all((P > 0) | (act[None, :] == 0), axis=1)
        Q = A[None] * jnp.maximum(P, 0.0)[:, None, :]
        V = _center(Q, act, mu)
        inv_c = act / jnp.where(act > 0, Q - V[..., None], 1.0)
        slope = mu * ((cnt[None, :, None] * A[None] * inv_c).sum(axis=1)
                      * dp[None, :]).sum(axis=1)
        good = ok & (slope >= 0)
        alpha = jnp.where(good.any(), alphas[jnp.argmax(good)], 0.0)
        p = p + alpha * dp
        p = p * total / (m * p).sum()
        mu = jnp.where(decrement < DECREMENT, jnp.maximum(mu * THETA, MU_MIN), mu)
        return p, mu

    return lax.fori_loop(0, SEG_ITERS, step, (p, mu))


# ---------------------------------------------------------------------------
# host phase: exact crossover and certificate
# ---------------------------------------------------------------------------


def _support_tree(R: Array, act: Array) -> Optional[List[List[int]]]:
    """Types of each row: its cheapest, plus the edges that join all active
    types into one tree, taken in order of how close the row is to a tie
    (Kruskal over ``price / speedup`` ratios). None if no tree exists."""
    g, k = R.shape
    best = R.argmin(axis=1)
    gap = R / R[np.arange(g), best][:, None] - 1.0
    gap[np.arange(g), best] = np.inf
    root = list(range(k))

    def find(a: int) -> int:
        while root[a] != a:
            a = root[a]
        return a

    types = [[int(b)] for b in best]
    need = int(act.sum()) - 1
    for e in np.argsort(gap, axis=None, kind="stable"):
        if need == 0:
            break
        i, j = divmod(int(e), k)
        if not np.isfinite(gap[i, j]):
            return None
        a, b = find(int(best[i])), find(j)
        if a != b:
            root[a] = b
            types[i].append(j)
            need -= 1
    return types if need == 0 else None


def _tree_prices(W: Array, types: List[List[int]], m: Array,
                 act: Array) -> Optional[Array]:
    """The prices at which every split row of the tree is indifferent
    between its types, normalized to ``m . p = sum(m)``; None unless all
    active prices are positive."""
    k = W.shape[1]
    ties = []
    for i, ts in enumerate(types):
        for j in ts[1:]:
            row = np.zeros(k)
            row[j], row[ts[0]] = 1.0 / W[i, j], -1.0 / W[i, ts[0]]
            ties.append(row)
    M = np.vstack(ties + [np.where(act, m, 0.0)])
    b = np.zeros(len(M))
    b[-1] = float(m[act].sum())
    price = np.linalg.lstsq(M, b, rcond=None)[0]
    if np.any(price[act] <= 0):
        return None
    return np.where(act, price, 0.0)


def _tree_primal(W: Array, cnt: Array, m: Array, types: List[List[int]],
                 act: Array) -> Optional[Array]:
    """Equal throughput and full capacity on the tree: single rows on their
    type, split rows' amounts and ``t`` from one small linear system; None
    when an amount comes out negative."""
    g, k = W.shape
    split = [i for i in range(g) if len(types[i]) > 1]
    single = np.ones(g, dtype=bool)
    single[split] = False
    best = np.asarray([ts[0] for ts in types])
    # unknowns: t, then each split row's amount on each of its types
    cols = [(i, j) for i in split for j in types[i]]
    A = np.zeros((len(split) + k, 1 + len(cols)))
    rhs = np.zeros(len(split) + k)
    A[:len(split), 0] = -1.0
    np.add.at(A[len(split):, 0], best[single],
              cnt[single] / W[single, best[single]])
    rhs[len(split):] = m
    rows = {i: r for r, i in enumerate(split)}
    for c, (i, j) in enumerate(cols, start=1):
        A[rows[i], c] = W[i, j]
        A[len(split) + j, c] = cnt[i]
    A[len(split):][~act] = 0.0
    sol = np.linalg.lstsq(A, rhs, rcond=None)[0]
    x = np.zeros((g, k))
    x[single, best[single]] = sol[0] / W[single, best[single]]
    for c, (i, j) in enumerate(cols, start=1):
        x[i, j] = sol[c]
    if sol[0] <= 0 or x.min() < -1e-12 * x.max():
        return None
    return np.maximum(x, 0.0)


def _tied_primal(W: Array, cnt: Array, m: Array, price: Array, t: float,
                 act: Array) -> Optional[Array]:
    """Equal throughput ``t`` and full capacity when more rows than the
    tree's tie exactly at ``price`` (distinct rows with equal speedup
    ratios): the tied rows' amounts over their tied types by non-negative
    least squares; None when no exact split exists."""
    from scipy.optimize import nnls

    g, k = W.shape
    R = np.where(act, price, np.inf) / W
    tied = R <= R.min(axis=1, keepdims=True) * (1.0 + 1e-9)
    free = tied.sum(axis=1) > 1
    best = R.argmin(axis=1)
    x = np.zeros((g, k))
    fixed = ~free
    x[fixed, best[fixed]] = t / W[fixed, best[fixed]]
    left = m - (cnt[:, None] * x).sum(axis=0)
    rows = np.flatnonzero(free)
    cols = [(r, j) for r, i in enumerate(rows) for j in np.flatnonzero(tied[i])]
    A = np.zeros((len(rows) + k, len(cols)))
    for c, (r, j) in enumerate(cols):
        A[r, c] = W[rows[r], j]
        A[len(rows) + j, c] = cnt[rows[r]]
    b = np.concatenate([np.full(len(rows), t), np.where(act, left, 0.0)])
    amounts, resid = nnls(A, b)
    if resid > 1e-12 * max(float(np.abs(b).max()), 1.0):
        return None
    for c, (r, j) in enumerate(cols):
        x[rows[r], j] = amounts[c]
    return x


def crossover(W: Array, cnt: Array, m: Array,
              p: Array) -> Optional[Tuple[Array, float, Array]]:
    """Exact answer from approximate prices ``p``, or None.

    Returns ``(x (g, k), t, prices)`` when a non-negative equal-throughput
    primal within capacity is found on the prices' tree (or, when distinct
    rows tie exactly, over every tied row) and the dual bound at the
    tree's exact prices is within ``CERT_TOL`` (relative) of its
    throughput.
    """
    g, k = W.shape
    act = m > 0
    if not act.any():
        return np.zeros((g, k)), 0.0, np.zeros(k)
    R = np.where(act, np.maximum(p, 1e-300), np.inf) / W
    types = _support_tree(R, act)
    if types is None:
        return None
    price = _tree_prices(W, types, m, act)
    if price is None:
        return None
    cheapest = (np.where(act, price, np.inf) / W).min(axis=1)
    bound = float(m @ price) / float(cnt @ cheapest)
    x = _tree_primal(W, cnt, m, types, act)
    if x is None:
        x = _tied_primal(W, cnt, m, price, bound, act)
        if x is None:
            return None
    # a feasible equal-throughput point: every row cut to the smallest
    # throughput, all scaled into capacity
    tput = np.einsum("lk,lk->l", W, x)
    used = (cnt[:, None] * x).sum(axis=0)
    scale = min(1.0, float(np.min(m[act] / np.maximum(used[act], 1e-300))))
    lower = scale * float(tput.min())
    if bound - lower > CERT_TOL * bound:
        return None
    return x, float(cnt @ tput) / float(cnt.sum()), price


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _reduce(W: Array) -> Tuple[Array, Array, Array]:
    """Group identical rows: (distinct W (g, k), inverse (n,), counts (g,))."""
    Wd, inv, cnt = np.unique(W, axis=0, return_inverse=True, return_counts=True)
    return Wd, inv.reshape(-1), cnt.astype(np.float64)


def _start(m: Array, price_hint: Optional[Array]) -> Tuple[Array, float]:
    """Starting prices and barrier weight: the hint's prices when they are
    usable on this fleet, else equal prices."""
    act = m > 0
    p = np.where(act, 1.0, 0.0)
    mu = MU_COLD
    if price_hint is not None:
        h = np.asarray(price_hint, dtype=np.float64)
        if h.shape == m.shape and np.all(np.isfinite(h)) and np.all(h[act] > 0):
            p, mu = np.where(act, h, 0.0), MU_WARM
    return p * float(m[act].sum()) / float(m @ p), mu


def solve_general(
    W: Array,
    m: Array,
    *,
    price_hint: Optional[Array] = None,
) -> Tuple[Array, float, Array, int]:
    """Certified optimum of Eq. 9 for any positive ``W``.

    Returns ``(X, t, prices, iters)``: the allocation in ``W``'s row order,
    the common throughput, the certified capacity prices (normalized to
    ``m . p = sum(m)``; pass them back as ``price_hint``) and the search
    iterations spent. Raises :class:`~repro.core.backends.BackendError` when
    no certificate is found within ``MAX_ITERS``.
    """
    W = np.asarray(W, dtype=np.float64)
    m = np.asarray(m, dtype=np.float64)
    n, k = W.shape
    Wd, inv, cnt = _reduce(W)
    g = Wd.shape[0]
    if not (m > 0).any():
        return np.zeros((n, k)), 0.0, np.zeros(k), 0
    G = bucket(n)
    A = np.ones((G, k), dtype=np.float64)
    A[:g] = 1.0 / Wd
    cntp = np.zeros(G, dtype=np.float64)
    cntp[:g] = cnt
    p, mu = _start(m, price_hint)
    key = A.shape
    fresh = key not in _COMPILED
    if fresh:
        _COMPILED.add(key)
        reg = obs_metrics.get_metrics()
        if reg is not None:
            reg.counter(f"jax.recompiles.general.b{G}").inc()
    iters = 0
    warm = price_hint is not None
    while iters < MAX_ITERS:
        if warm and iters >= WARM_ITERS:
            # the hint was too far from this instance's prices
            p, mu = _start(m, None)
            warm = False
        with obs_trace.span("search", "jax", tier="general", bucket=G,
                            compile=fresh):
            with x64_scope():
                p_dev, mu_dev = _search_segment(A, cntp, m, p, np.float64(mu))
                p = np.asarray(p_dev)
                mu = float(mu_dev)
        fresh = False
        iters += SEG_ITERS
        with obs_trace.span("crossover", "jax", tier="general", rows=g):
            got = crossover(Wd, cnt, m, p)
        if got is not None:
            x, t, price = got
            return x[inv], t, price, iters
    raise backends.BackendError(
        f"general non-cooperative search did not certify within {MAX_ITERS} "
        f"iterations (n={n}, {g} distinct rows)")


def prewarm(n_max: int, k: int) -> List[int]:
    """Compile the search segment for every bucket up to ``bucket(n_max)``
    (mirrors ``jax_solve.prewarm``). Returns the bucket sizes compiled."""
    sizes = []
    s = bucket(1)
    while s < bucket(n_max):
        sizes.append(s)
        s *= 2
    sizes.append(bucket(n_max))
    with obs_trace.span("prewarm", "jax", tier="general", buckets=len(sizes)):
        with x64_scope():
            for G in sizes:
                p, _ = _search_segment(np.ones((G, k)), np.ones(G),
                                       np.full(k, 2.0), np.ones(k),
                                       np.float64(MU_COLD))
                p.block_until_ready()
                _COMPILED.add((G, k))
    return sizes
