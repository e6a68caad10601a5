"""Pairwise tenant envy-gap matrix, Pallas TPU kernel.

The cooperative OEF program (Eq. 10) is an LP whose fairness constraints are
the pairwise envy gaps

    E[l, i] = W_l . x_i - W_l . x_l        (feasible iff E <= 0 for l != i)

and the primal–dual solver in ``core.jax_coop`` evaluates the full (n, n)
gap matrix once per iteration — it is both the dual-update operand and the
feasibility residual, so it is the iteration's dominant FLOP block. The
reduction is a plain rank-k product with a rank-1 correction:

    E = W @ X^T - diag(W @ X^T) 1^T

Kernel layout: grid = (l_tiles, i_tiles), each program instance producing one
(block_l, block_i) output tile from three operand tiles — ``W`` rows for the
envious block, ``X`` rows for the envied block, and ``X`` rows for the
envious block again (to form the "own throughput" diagonal term without a
second pass). The type axis ``k`` is small (device catalog) and kept whole
inside every tile.

The wrapper picks tiles that divide both tenant axes; the caller masks the
diagonal and any padded rows (``core.jax_coop`` multiplies by its pair
mask). On CPU the kernel runs with ``interpret=True``. On TPU it compiles in
float32 only: Mosaic has no float64, so the float64 solves of
``core.jax_coop`` run the jnp reference path (:func:`envy_gaps_ref`, same
math and op order) on every platform, and the kernel is validated against it
in tests/test_jax_coop.py. Same contract as ``kernels/waterfill.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _envy_kernel(w_ref, xi_ref, xl_ref, e_ref):
    w = w_ref[...]        # (block_l, k) speedups of the envious rows
    xi = xi_ref[...]      # (block_i, k) bundles of the envied rows
    xl = xl_ref[...]      # (block_l, k) bundles of the envious rows
    own = jnp.sum(w * xl, axis=1)  # (block_l,)
    cross = jnp.dot(w, xi.T, preferred_element_type=w.dtype)
    e_ref[...] = cross - own[:, None]


def envy_gaps(W, X, *, block_l: int = 128, block_i: int = 128,
              interpret: bool = False):
    """Envy-gap matrix ``E[l, i] = W_l.x_i - W_l.x_l`` via the tiled kernel.

    W: (n, k) speedup rows; X: (n, k) allocation bundles, same row order.
    Returns the full (n, n) matrix; the diagonal is exactly zero in exact
    arithmetic (caller masks it — ``jax_coop`` zeroes it with its pair mask).
    """
    n, k = W.shape
    if X.shape != W.shape:
        raise ValueError(f"W and X must share (n, k); got {W.shape} vs {X.shape}")
    bl = min(block_l, n)
    while n % bl:
        bl //= 2
    bi = min(block_i, n)
    while n % bi:
        bi //= 2
    return pl.pallas_call(
        _envy_kernel,
        grid=(n // bl, n // bi),
        in_specs=[
            pl.BlockSpec((bl, k), lambda l, i: (l, 0)),
            pl.BlockSpec((bi, k), lambda l, i: (i, 0)),
            pl.BlockSpec((bl, k), lambda l, i: (l, 0)),
        ],
        out_specs=pl.BlockSpec((bl, bi), lambda l, i: (l, i)),
        out_shape=jax.ShapeDtypeStruct((n, n), W.dtype),
        interpret=interpret,
    )(W, X, X)


def envy_gaps_ref(W, X):
    """jnp reference path: same math and op order as the kernel. This is the
    production path on every platform."""
    own = jnp.sum(W * X, axis=1)
    return jnp.dot(W, X.T, preferred_element_type=W.dtype) - own[:, None]
