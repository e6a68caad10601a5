"""RG-LRU linear-recurrence scan, Pallas TPU kernel.

The recurrence h_t = a_t * h_{t-1} + b_t is memory-bound (2 reads + 1 write
per element, O(1) FLOPs). TPU adaptation: tile the *feature* dim across the
grid (each lane-dim tile is 128-aligned for the VPU), keep the running state
in VMEM scratch, and walk time sequentially inside the kernel in blocks —
the sequential dependency is on the (cheap) scalar chain, while each step is
a full-width vector op. The feature-parallel grid gives the same parallelism
the GPU version gets from thread blocks without needing warp shuffles.

Oracle: ``ref.rglru_scan_ref`` (sequential lax.scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, *, seq: int, block_t: int):
    h = h0_ref[...].astype(jnp.float32)[None, :]  # (1, block_d)

    def body(t0, h):
        def step(i, h):
            t = t0 * block_t + i
            a = a_ref[pl.ds(t, 1), :].astype(jnp.float32)
            b = b_ref[pl.ds(t, 1), :].astype(jnp.float32)
            h = a * h + b
            o_ref[pl.ds(t, 1), :] = h.astype(o_ref.dtype)
            return h

        return jax.lax.fori_loop(0, block_t, step, h)

    jax.lax.fori_loop(0, seq // block_t, body, h)


@functools.partial(jax.jit, static_argnames=("block_d", "block_t", "interpret"))
def rglru_scan(
    a: jnp.ndarray,  # (B, S, D)
    b: jnp.ndarray,
    h0: jnp.ndarray,  # (B, D)
    *,
    block_d: int = 128,
    block_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, D = a.shape
    if D % block_d:
        raise ValueError(
            f"feature dim D={D} must be divisible by block_d={block_d}; "
            f"pass a block_d that divides the model width"
        )
    bt = min(block_t, S)
    while S % bt:
        bt //= 2
    kernel = functools.partial(_rglru_kernel, seq=S, block_t=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, D // block_d),
        in_specs=[
            pl.BlockSpec((None, S, block_d), lambda bi, di: (bi, 0, di)),
            pl.BlockSpec((None, S, block_d), lambda bi, di: (bi, 0, di)),
            pl.BlockSpec((None, block_d), lambda bi, di: (bi, di)),
        ],
        out_specs=pl.BlockSpec((None, S, block_d), lambda bi, di: (bi, 0, di)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        interpret=interpret,
    )(a, b, h0.reshape(B, D))
