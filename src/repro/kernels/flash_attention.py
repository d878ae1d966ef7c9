"""Pallas TPU flash attention (causal / sliding-window, GQA).

TPU adaptation of FlashAttention (arXiv:2205.14135): online-softmax
accumulation in VMEM scratch across the sequential last grid dimension
(TPU grids iterate minor-most last, so scratch persists across k-blocks),
MXU-aligned block shapes, fp32 accumulation.  Block-level pruning skips
(q-block, k-block) pairs that are fully masked (causal upper triangle,
sliding-window lower band) — the kernel is O(S*W) for window attention.

Layout: q (BH, S, d), k/v (BK, T, d); grid (BH, nq, nk).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    num_kb: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = iq * block_q
    k_start = ik * block_k

    relevant = jnp.asarray(True)
    if causal:
        relevant = relevant & (k_start <= q_start + block_q - 1)
    if window is not None:
        relevant = relevant & (k_start + block_k - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok = ok & (kpos <= qpos)
        if window is not None:
            ok = ok & (kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scratch[:, :1]  # (bq, 1)
        l_prev = l_scratch[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        p = jnp.exp(s - m_new)  # (bq, bk); rows with no valid key ~ exp(0)=1*0-mask
        p = jnp.where(ok, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[...] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(ik == num_kb - 1)
    def _finalize():
        l = l_scratch[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (BH, S, d)
    k: jax.Array,  # (BK, T, d)
    v: jax.Array,  # (BK, T, d)
    *,
    n_q_per_kv: int,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    bh, s, d = q.shape
    bk_heads, t, _ = k.shape
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    nq, nk = s // block_q, t // block_k

    def q_index(ib, iq, ik):
        return (ib, iq, 0)

    def kv_index(ib, iq, ik):
        return (ib // n_q_per_kv, ik, 0)

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_kb=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
