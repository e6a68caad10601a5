"""Water-filling feasibility reduction, Pallas TPU kernel.

The exact non-cooperative OEF solver (``core.oef.solve_noncoop_fast``) finds
the common throughput level tau* by bisection on a greedy feasibility check.
The greedy consumes the capacity "tape" (device types fastest->slowest, users
fastest->slowest) strictly in order, which makes the per-tau check expressible
as k vectorized passes instead of an n-user Python loop: processing types
fastest-first, the devices a user can still take from type j is

    take[u, j] = clip(m_j - cumsum_excl_u(r / w_j), 0, r_u / w_{u,j})

where ``r`` is the per-user remaining throughput need (initially tau) and the
exclusive cumsum runs over users sorted fastest-first — capacity consumed by
faster users before user u reaches the tape. After the k passes the
*feasibility mass* ``sum_u r_u`` is ~0 iff tau is achievable. The bisection
driver in ``core.jax_solve`` evaluates a whole tile of candidate taus per
step, so the reduction is batched (lanes x users).

Kernel layout: grid = (k, user_tiles) with the type axis outer and the user
axis innermost (both sequential on TPU) — each type pass must see every user
tile before the next type starts. Every block satisfies the TPU tiling rule
(last two block dimensions divisible by 8 and 128, or equal to the array's):

  - ``Wf`` goes in transposed, ``(k, n)``, as ``(k, block_u)`` blocks: the
    whole type axis rides in each block and type step ``j`` picks its row
    (column ``k-1-j`` of ``Wf``, fastest type first) inside the kernel;
  - ``m`` (k,) sits whole in scalar memory and is read by index;
  - the candidate taus go in whole as a ``(T, 1)`` column, the mask as a
    ``(1, block_u)`` row;
  - the remaining need ``r`` lives in a VMEM scratch holding every user
    tile, so a tile revisited on the next type step reads what it wrote;
    ``cum``, the running device consumption of the current type, is a
    ``(T, 1)`` scratch reset at each new type; ``mass`` (T, 1) is the
    output block, accumulated on the last type.

The wrapper pads users to a tile multiple (padded users get mask=0 so their
need starts at 0 and they never consume capacity). On CPU the kernel runs
with ``interpret=True``. On TPU it compiles in float32 only: Mosaic has no
float64, so the float64 solves of ``core.jax_solve`` run the jnp reference
path (:func:`waterfill_masses_ref`, same math) on every platform, and the
kernel is validated against it in tests/test_jax_solve.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Guard against division blow-up for degenerate speedups, same constant as
# the numpy greedy in core/oef.py.
_W_FLOOR = 1e-300


def _waterfill_kernel(m_ref, tau_ref, w_ref, mask_ref, mass_ref, r_scr, cum_scr,
                      *, n_k: int):
    j = pl.program_id(0)  # type step (0 = fastest type)
    u = pl.program_id(1)  # user tile (0 = fastest users)
    col = n_k - 1 - j  # types ascend slow->fast, the tape runs fast->slow

    @pl.when(j == 0)
    def _init_need():
        r_scr[u] = tau_ref[...] * mask_ref[...]

    @pl.when(u == 0)
    def _reset_type_consumption():
        cum_scr[...] = jnp.zeros_like(cum_scr)

    @pl.when((j == 0) & (u == 0))
    def _init_mass():
        mass_ref[...] = jnp.zeros_like(mass_ref)

    w = jnp.maximum(w_ref[pl.ds(col, 1), :], _W_FLOOR)  # (1, block_u)
    r = r_scr[u]  # (T, block_u)
    dev = r / w  # device demand if served entirely by this type
    # exclusive cumsum over the tile's users as a strictly-upper-triangular
    # matmul: Mosaic lowers no cumsum, and the MXU does this one for free
    bu = dev.shape[1]
    before = (jax.lax.broadcasted_iota(jnp.int32, (bu, bu), 0)
              < jax.lax.broadcasted_iota(jnp.int32, (bu, bu), 1))
    cum_excl = cum_scr[...] + jnp.dot(
        dev, before.astype(dev.dtype), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=dev.dtype)
    take = jnp.clip(m_ref[col] - cum_excl, 0.0, dev)
    r = r - take * w
    r_scr[u] = r
    cum_scr[...] = cum_scr[...] + dev.sum(axis=1, keepdims=True)

    @pl.when(j == n_k - 1)
    def _accumulate_mass():
        mass_ref[...] = mass_ref[...] + r.sum(axis=1, keepdims=True)


def waterfill_masses(taus, Wf, m, mask, *, block_u: int = 128,
                     interpret: bool = False):
    """Leftover feasibility mass per candidate tau, via the tiled kernel.

    taus: (T,) candidate equal-throughput levels;
    Wf:   (n, k) speedup rows sorted FASTEST USER FIRST (the caller holds the
          permutation; ``core.jax_solve`` reverses its slowest-first sort);
    m:    (k,) per-type capacity, types ascending slow->fast as everywhere;
    mask: (n,) 1.0 for real users, 0.0 for padding rows.

    Returns (T,) ``sum_u r_u`` after the k greedy passes; ~0 => tau feasible.
    """
    T = taus.shape[0]
    n, k = Wf.shape
    bu = n if n <= block_u else block_u
    n_pad = -(-n // bu) * bu
    WfT = jnp.pad(Wf.T, ((0, 0), (0, n_pad - n)), constant_values=1.0)
    mask = jnp.pad(mask, (0, n_pad - n))
    kernel = functools.partial(_waterfill_kernel, n_k=k)
    mass = pl.pallas_call(
        kernel,
        grid=(k, n_pad // bu),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((T, 1), lambda j, u: (0, 0)),
            pl.BlockSpec((k, bu), lambda j, u: (0, u)),
            pl.BlockSpec((1, bu), lambda j, u: (0, u)),
        ],
        out_specs=pl.BlockSpec((T, 1), lambda j, u: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, 1), taus.dtype),
        scratch_shapes=[
            pltpu.VMEM((n_pad // bu, T, bu), taus.dtype),  # remaining need
            pltpu.VMEM((T, 1), taus.dtype),  # consumption of the current type
        ],
        interpret=interpret,
    )(m, taus[:, None], WfT, mask[None, :])
    return mass[:, 0]


def _cumsum(x, axis):
    """Inclusive prefix sum as a log-depth associative scan. ``jnp.cumsum``
    on float64 takes the TPU compiler about three minutes per call site at
    128 users and up; the scan compiles in under a second."""
    return jax.lax.associative_scan(jnp.add, x, axis=axis)


def waterfill_masses_ref(taus, Wf, m, mask):
    """jnp reference path: same math as the kernel (whose exclusive cumsum is
    a triangular matmul), unrolled over the (static, small) type axis. This
    is the production path on every platform."""
    k = Wf.shape[1]
    r = taus[:, None] * mask[None, :]
    for j in range(k - 1, -1, -1):
        w = jnp.maximum(Wf[:, j], _W_FLOOR)
        dev = r / w[None, :]
        cum_excl = _cumsum(dev, axis=1) - dev
        take = jnp.clip(m[j] - cum_excl, 0.0, dev)
        r = r - take * w[None, :]
    return r.sum(axis=1)


def waterfill_allocate(tau, Wf, m, mask):
    """Materialize the staircase allocation X (n, k) at throughput ``tau``.

    One extra greedy pass at the converged tau, emitting the per-type takes
    instead of only the leftover mass. Row order matches ``Wf`` (fastest
    user first); padded rows receive zero.
    """
    n, k = Wf.shape
    r = tau * mask
    cols = [None] * k
    for j in range(k - 1, -1, -1):
        w = jnp.maximum(Wf[:, j], _W_FLOOR)
        dev = r / w
        cum_excl = _cumsum(dev, axis=0) - dev
        take = jnp.clip(m[j] - cum_excl, 0.0, dev)
        cols[j] = take
        r = r - take * w
    return jnp.stack(cols, axis=1)
