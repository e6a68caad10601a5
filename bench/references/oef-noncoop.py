"""Plain reference for non-cooperative OEF (the paper's Eq. 9), and the
numbers that compare an allocation with it.

    maximize   sum_{l,j} w_lj x_lj
    s.t.       sum_l x_lj <= m_j             (capacity)
               W_l . x_l == W_0 . x_0         (equal normalized throughput)
               x >= 0

One sparse linear program for scipy's HiGHS, in float64. It imports nothing
of the program under test. The optimum ``X`` need not be unique, so an
allocation is judged by its objective against the optimum and by the
program's own guarantees, not entry by entry.
"""
from __future__ import annotations

from typing import Dict

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog


def solve(W: np.ndarray, m: np.ndarray) -> np.ndarray:
    """The optimal allocation ``X`` (n, k) of Eq. 9."""
    n, k = W.shape
    cols = np.arange(n * k)
    A_cap = sp.csr_matrix((np.ones(n * k), (cols % k, cols)), shape=(k, n * k))
    rows = np.repeat(np.arange(n - 1), 2 * k)
    idx = np.concatenate([np.arange(1, n)[:, None] * k + np.arange(k),
                          np.zeros((n - 1, 1), dtype=np.int64) + np.arange(k)],
                         axis=1).ravel()
    vals = np.concatenate([W[1:], -np.broadcast_to(W[0], (n - 1, k))],
                          axis=1).ravel()
    A_eq = sp.csr_matrix((vals, (rows, idx)), shape=(n - 1, n * k))
    res = linprog(-W.ravel(), A_ub=A_cap, b_ub=m,
                  A_eq=A_eq if n > 1 else None,
                  b_eq=np.zeros(n - 1) if n > 1 else None,
                  bounds=(0, None), method="highs")
    if res.status != 0:
        raise RuntimeError(f"reference LP failed: {res.message}")
    return res.x.reshape(n, k)


def numbers(W: np.ndarray, m: np.ndarray, X: np.ndarray,
            X_ref: np.ndarray) -> Dict[str, float]:
    """How far ``X`` is from the reference optimum and from Eq. 9's rows:

    - ``obj_gap``: relative gap of total throughput to the optimum;
    - ``tput_spread``: largest relative departure of a tenant's throughput
      ``W_l . x_l`` from their mean (Eq. 9 makes them equal);
    - ``cap_excess``: largest relative excess over a type's capacity.
    """
    t = np.einsum("lk,lk->l", W, X)
    opt = float((W * X_ref).sum())
    mean = float(t.mean())
    return {
        "obj_gap": abs(float(t.sum()) - opt) / opt,
        "tput_spread": float(np.abs(t - mean).max()) / mean,
        "cap_excess": max(0.0, float(((X.sum(axis=0) - m) / m).max())),
    }
