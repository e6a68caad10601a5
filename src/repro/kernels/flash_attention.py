"""Flash attention forward, Pallas TPU kernel.

TPU-native adaptation of the (GPU-origin) FlashAttention tiling: the online-
softmax accumulation runs over KV tiles staged HBM->VMEM by ``pl.pallas_call``
BlockSpecs, with MXU-aligned (128-multiple) tile shapes. Grid is
(batch*heads, q_tiles); each program holds one (block_q, D) query tile and a
fp32 accumulator in VMEM scratch while looping over KV tiles with
``jax.lax.fori_loop``. Causal masking prunes fully-masked KV tiles.

Validated on CPU with ``interpret=True`` against ``ref.attention_ref``
(tests/test_kernels.py sweeps shapes and dtypes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                  seq_k: int, causal: bool, window: int | None, sm_scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale  # (block_q, D)
    D = q.shape[-1]
    n_kv = seq_k // block_k

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (block_q, block_k)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        diff = q_pos - k_pos
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= diff >= 0
        if window is not None:
            mask &= diff < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, D), jnp.float32)

    if causal:
        # skip KV tiles strictly above the diagonal of this q tile
        last_k = jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k, n_kv)
    else:
        last_k = n_kv
    first_k = 0
    if window is not None:
        first_k = jnp.maximum((qi * block_q - window) // block_k, 0)
    m, l, acc = jax.lax.fori_loop(first_k, last_k, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "window", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # (B, H, Sq, D)
    k: jnp.ndarray,  # (B, H, Sk, D)
    v: jnp.ndarray,
    *,
    block_q: int = 128,
    block_k: int = 128,
    causal: bool = True,
    window: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"sequence lengths (Sq={Sq}, Sk={Sk}) must be divisible by the "
            f"tile shapes (block_q={block_q}, block_k={block_k}); pad the "
            f"inputs or pass smaller blocks"
        )
    sm_scale = 1.0 / math.sqrt(D)
    BH = B * H
    qf = q.reshape(BH, Sq, D)
    kf = k.reshape(BH, Sk, D)
    vf = v.reshape(BH, Sk, D)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_k=Sk,
        causal=causal, window=window, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
