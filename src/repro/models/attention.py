"""Attention mixers: GQA (full / sliding-window) and MLA (DeepSeek-V2).

All functions are pure; caches are explicit pytrees.  Shapes:
  x        (B, S, D)
  q        (B, S, K, G, h)   K = kv heads, G = query heads per kv head
  k, v     (B, T, K, h)
Decode steps take a cache pytree + scalar ``index`` (tokens already cached).
Batched serving decodes one token for every sequence per call; all sequences
in the batch share the cache length (continuous batching is handled a level
up, in ``repro.dist.serve``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.jax_events import scope

from .layers import apply_rope, dense_init, rms_norm, rope_tables

Params = Dict[str, Any]

NEG_INF = -2.3819763e38  # large negative for masking (fits bf16/f32)


def _constrain(x: jax.Array, spec) -> jax.Array:
    """Best-effort sharding constraint (no-op without a mesh context).

    GSPMD's propagation gives up on the 5D grouped-GQA einsums and falls
    back to full replication of q/scores (a multi-GB all-gather per layer at
    32k context); pinning q and the score tensor to sequence-sharding keeps
    attention in the Megatron-SP regime: each device computes its query
    slice against (gathered, cheap) K/V."""
    if spec is None:
        return x
    from jax.sharding import PartitionSpec as P

    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, TypeError):
        return x


# ----------------------------------------------------------------------------
# GQA
# ----------------------------------------------------------------------------

def gqa_init(
    key,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    qkv_bias: bool = False,
    qk_norm: bool = False,
) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(k1, d_model, n_heads * head_dim),
        "wk": dense_init(k2, d_model, n_kv_heads * head_dim),
        "wv": dense_init(k3, d_model, n_kv_heads * head_dim),
        "wo": dense_init(k4, n_heads * head_dim, d_model),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), jnp.float32)
        p["k_norm"] = jnp.zeros((head_dim,), jnp.float32)
    return p


def _project_qkv(params: Params, x: jax.Array, n_heads: int, n_kv_heads: int, head_dim: int):
    dtype = x.dtype
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    q = q.reshape(b, s, n_heads, head_dim)
    k = k.reshape(b, s, n_kv_heads, head_dim)
    v = v.reshape(b, s, n_kv_heads, head_dim)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    return q, k, v


def _sdpa(
    q: jax.Array,  # (B, S, K, G, h)
    k: jax.Array,  # (B, T, K, h)
    v: jax.Array,  # (B, T, K, h)
    mask: jax.Array,  # (S, T) or (B, S, T) additive fp32
    scale: float,
    act_pspec=None,
) -> jax.Array:
    dtype = q.dtype
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * scale
    if act_pspec is not None and scores.shape[3] > 1:
        b_ax, s_ax = act_pspec
        scores = _constrain(scores, (b_ax, None, None, s_ax, None))
    while mask.ndim < scores.ndim:
        mask = mask[None]
    scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out


def causal_mask(s: int, t: int, offset: int = 0, window: Optional[int] = None) -> jax.Array:
    """Additive mask; query i (absolute position offset+i) sees key j<=i,
    and only keys within ``window`` positions when set (sliding window)."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa_q_chunked(
    q: jax.Array,  # (B, S, K, G, h)
    k: jax.Array,  # (B, T, K, h)
    v: jax.Array,  # (B, T, K, h)
    scale: float,
    *,
    causal: bool,
    window: Optional[int],
    chunk: int,
    act_pspec=None,
) -> jax.Array:
    """Query-chunked attention: ``lax.scan`` over query blocks bounds the
    live score tensor to (B,K,G,chunk,T) — the XLA-level flash-attention
    adaptation used when the Pallas kernel path is off.  The scan body is
    ``jax.checkpoint``-ed so backward recomputes one block's scores at a
    time instead of saving them all.

    Note for cost accounting: XLA's cost model counts a scan body ONCE, so
    this path undercounts attention FLOPs by ~nq; the roofline harness
    therefore lowers with ``attn_chunk_q=0`` (identical math, fully costed)
    while dry-run memory proofs use this path (see benchmarks/roofline.py)."""
    b, s, kh, g, h = q.shape
    t = k.shape[1]
    assert s % chunk == 0, (s, chunk)
    nq = s // chunk
    qc = q.reshape(b, nq, chunk, kh, g, h).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def body(carry, args):
        iq, qblk = args
        qpos = iq * chunk + jnp.arange(chunk)[:, None]
        ok = jnp.ones((chunk, t), bool)
        if causal:
            ok = ok & (kpos <= qpos)
        if window is not None:
            ok = ok & (kpos > qpos - window)
        mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
        return carry, _sdpa(qblk, k, v, mask, scale, act_pspec=act_pspec)

    _, out = jax.lax.scan(body, None, (jnp.arange(nq), qc))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, kh, g, h)


def _softmax_scale(scale: Optional[float], head_dim: int) -> float:
    return 1.0 / math.sqrt(head_dim) if scale is None else scale


def gqa_apply(
    params: Params,
    x: jax.Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Optional[float] = 10000.0,
    causal: bool = True,
    window: Optional[int] = None,
    positions: Optional[jax.Array] = None,
    chunk_q: int = 0,
    use_flash_kernel: bool = False,
    act_pspec=None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Self-attention over a full sequence (training / prefill).
    ``rope_theta=None``: no rotary embedding (NoPE); ``scale=None``: the
    softmax scale is 1 / sqrt(head_dim)."""
    dtype = x.dtype
    b, s, d = x.shape
    g = n_heads // n_kv_heads
    with scope("attn_proj"):
        q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
        if rope_theta is not None:
            if positions is None:
                positions = jnp.arange(s)
            cos, sin = rope_tables(positions, head_dim, rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if act_pspec is not None:
            b_ax, s_ax = act_pspec
            q = _constrain(q, (b_ax, s_ax, None, None))  # query: SP over seq
            k = _constrain(k, (b_ax, None, None, None))  # K/V: gathered once
            v = _constrain(v, (b_ax, None, None, None))
        scale = _softmax_scale(scale, head_dim)
        with scope("attn_core"):
            # Mosaic kernels cannot be partitioned automatically: a step
            # sharded over a mesh keeps the XLA path.
            if use_flash_kernel and act_pspec is None:
                from repro.kernels import ops as _kops

                out = _kops.flash_attention(q, k, v, causal=causal, window=window, scale=scale)
            else:
                q = q.reshape(b, s, n_kv_heads, g, head_dim)
                if chunk_q and s > chunk_q and s % chunk_q == 0:
                    out = _sdpa_q_chunked(q, k, v, scale, causal=causal, window=window,
                                          chunk=chunk_q, act_pspec=act_pspec)
                else:
                    if causal:
                        mask = causal_mask(s, s, window=window)
                    else:
                        mask = jnp.zeros((s, s), jnp.float32)
                    out = _sdpa(q, k, v, mask, scale, act_pspec=act_pspec)
        out = out.reshape(b, s, n_heads * head_dim)
        return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dtype))


def cross_attention_apply(
    params: Params,
    x: jax.Array,
    kv_source: Tuple[jax.Array, jax.Array],
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
) -> jax.Array:
    """Cross-attention with precomputed K/V (whisper decoder)."""
    dtype = x.dtype
    b, s, _ = x.shape
    g = n_heads // n_kv_heads
    k, v = kv_source
    t = k.shape[1]
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
    q = q.reshape(b, s, n_kv_heads, g, head_dim)
    mask = jnp.zeros((s, t), jnp.float32)
    out = _sdpa(q, k, v, mask, 1.0 / math.sqrt(head_dim))
    out = out.reshape(b, s, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dtype))


def cross_kv(params: Params, enc: jax.Array, n_kv_heads: int, head_dim: int):
    dtype = enc.dtype
    b, t, _ = enc.shape
    k = jnp.einsum("btd,dh->bth", enc, params["wk"].astype(dtype))
    v = jnp.einsum("btd,dh->bth", enc, params["wv"].astype(dtype))
    if "bk" in params:
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    return k.reshape(b, t, n_kv_heads, head_dim), v.reshape(b, t, n_kv_heads, head_dim)


# -- caches -------------------------------------------------------------------

def gqa_cache_init(
    batch: int, max_len: int, n_kv_heads: int, head_dim: int, dtype=jnp.bfloat16
) -> Params:
    shape = (batch, max_len, n_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_prefill_cache(
    params: Params,
    x: jax.Array,
    max_len: int,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Optional[float] = 10000.0,
    window: Optional[int] = None,
    cache_dtype=None,
) -> Params:
    """Compute K/V for a prompt and lay it into a fresh cache.

    Window layers keep a ring buffer of the last ``window`` positions, so the
    cache is (B, min(window, max_len), K, h) — this is what makes 500k-token
    contexts feasible for local-attention architectures."""
    b, s, _ = x.shape
    dtype = cache_dtype or x.dtype
    _, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if rope_theta is not None:
        cos, sin = rope_tables(jnp.arange(s), head_dim, rope_theta)
        k = apply_rope(k, cos, sin)
    if window is not None and window < max_len:
        w = window
        cache = gqa_cache_init(b, w, n_kv_heads, head_dim, dtype)
        # last w positions land at slot p % w
        take = min(s, w)
        tail_k = k[:, -take:].astype(dtype)
        tail_v = v[:, -take:].astype(dtype)
        slot = (jnp.arange(s - take, s)) % w
        cache["k"] = cache["k"].at[:, slot].set(tail_k)
        cache["v"] = cache["v"].at[:, slot].set(tail_v)
        return cache
    cache = gqa_cache_init(b, max_len, n_kv_heads, head_dim, dtype)
    cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k.astype(dtype), (0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v.astype(dtype), (0, 0, 0, 0))
    return cache


def gqa_decode(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    cache: Params,
    index: jax.Array,  # scalar int32: number of tokens already in cache
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Optional[float] = 10000.0,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, Params]:
    dtype = x.dtype
    b = x.shape[0]
    g = n_heads // n_kv_heads
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if rope_theta is not None:
        cos, sin = rope_tables(jnp.asarray(index)[None], head_dim, rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    t = cache["k"].shape[1]
    if window is not None and t <= window:
        slot = jnp.mod(index, t)
    else:
        slot = index
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))

    if window is not None and t <= window:
        # ring buffer: slot j holds absolute position p_j = index - ((index - j) mod t)
        j = jnp.arange(t)
        p = index - jnp.mod(index - j, t)
        mask = jnp.where(p >= 0, 0.0, NEG_INF).astype(jnp.float32)[None, :]  # (1, t)
    else:
        j = jnp.arange(t)
        mask = jnp.where(j <= index, 0.0, NEG_INF).astype(jnp.float32)[None, :]

    q = q.reshape(b, 1, n_kv_heads, g, head_dim)
    out = _sdpa(q, ck.astype(dtype), cv.astype(dtype), mask, _softmax_scale(scale, head_dim))
    out = out.reshape(b, 1, n_heads * head_dim)
    out = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dtype))
    return out, {"k": ck, "v": cv}


# ----------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434)
# ----------------------------------------------------------------------------

def mla_init(
    key,
    d_model: int,
    n_heads: int,
    q_lora_rank: int,
    kv_lora_rank: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
) -> Params:
    ks = jax.random.split(key, 6)
    dn, dr, dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    return {
        "wq_a": dense_init(ks[0], d_model, q_lora_rank),
        "q_norm": jnp.zeros((q_lora_rank,), jnp.float32),
        "wq_b": dense_init(ks[1], q_lora_rank, n_heads * (dn + dr)),
        "wkv_a": dense_init(ks[2], d_model, kv_lora_rank + dr),
        "kv_norm": jnp.zeros((kv_lora_rank,), jnp.float32),
        "wkv_b": dense_init(ks[3], kv_lora_rank, n_heads * (dn + dv)),
        "wo": dense_init(ks[4], n_heads * dv, d_model),
    }


def _mla_qkv(params: Params, x: jax.Array, n_heads: int, dims: Tuple[int, int, int]):
    """Returns (q_nope, q_rope, c_kv, k_rope) before rope application."""
    dn, dr, dv = dims
    dtype = x.dtype
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dr->bsr", x, params["wq_a"].astype(dtype))
    q = rms_norm(q, params["q_norm"])
    q = jnp.einsum("bsr,rh->bsh", q, params["wq_b"].astype(dtype))
    q = q.reshape(b, s, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(dtype))
    c_kv, k_rope = kv[..., : kv.shape[-1] - dr], kv[..., kv.shape[-1] - dr :]
    c_kv = rms_norm(c_kv, params["kv_norm"])
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(params: Params, c_kv: jax.Array, n_heads: int, dims: Tuple[int, int, int]):
    dn, dr, dv = dims
    dtype = c_kv.dtype
    b, t, _ = c_kv.shape
    kv = jnp.einsum("btr,rh->bth", c_kv, params["wkv_b"].astype(dtype))
    kv = kv.reshape(b, t, n_heads, dn + dv)
    return kv[..., :dn], kv[..., dn:]  # k_nope, v


def mla_apply(
    params: Params,
    x: jax.Array,
    *,
    n_heads: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    rope_theta: float = 10000.0,
    positions: Optional[jax.Array] = None,
    chunk_q: int = 0,
    act_pspec=None,
) -> jax.Array:
    dims = (qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
    dn, dr, dv = dims
    dtype = x.dtype
    b, s, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, n_heads, dims)
    if positions is None:
        positions = jnp.arange(s)
    cos, sin = rope_tables(positions, dr, rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]  # shared rope head
    k_nope, v = _mla_expand_kv(params, c_kv, n_heads, dims)
    scale = 1.0 / math.sqrt(dn + dr)
    if act_pspec is not None:
        b_ax, s_ax = act_pspec
        q_nope = _constrain(q_nope, (b_ax, s_ax, None, None))
        q_rope = _constrain(q_rope, (b_ax, s_ax, None, None))
        k_nope = _constrain(k_nope, (b_ax, None, None, None))
        v = _constrain(v, (b_ax, None, None, None))

    def attend(qn, qr, offset):
        sq = qn.shape[1]
        scores = (
            jnp.einsum("bshn,bthn->bhst", qn, k_nope)
            + jnp.einsum("bshr,btr->bhst", qr, k_rope)
        ).astype(jnp.float32) * scale
        if act_pspec is not None and sq > 1:
            b_ax, s_ax = act_pspec
            scores = _constrain(scores, (b_ax, None, s_ax, None))
        scores = scores + causal_mask(sq, s, offset=offset)[None, None]
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bhst,bthv->bshv", probs, v)

    if chunk_q and s > chunk_q and s % chunk_q == 0:
        nq = s // chunk_q
        qn_c = q_nope.reshape(b, nq, chunk_q, n_heads, dn).transpose(1, 0, 2, 3, 4)
        qr_c = q_rope.reshape(b, nq, chunk_q, n_heads, dr).transpose(1, 0, 2, 3, 4)

        @jax.checkpoint
        def body(carry, args):
            iq, qn, qr = args
            return carry, attend(qn, qr, iq * chunk_q)

        _, out = jax.lax.scan(body, None, (jnp.arange(nq), qn_c, qr_c))
        out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, n_heads * dv)
    else:
        out = attend(q_nope, q_rope, 0).reshape(b, s, n_heads * dv)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dtype))


def mla_cache_init(batch: int, max_len: int, kv_lora_rank: int, qk_rope_head_dim: int, dtype=jnp.bfloat16) -> Params:
    # The MLA selling point: cache only the compressed latent + shared rope key.
    return {
        "c_kv": jnp.zeros((batch, max_len, kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, qk_rope_head_dim), dtype),
    }


def mla_prefill_cache(
    params: Params,
    x: jax.Array,
    max_len: int,
    *,
    n_heads: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    rope_theta: float = 10000.0,
    cache_dtype=None,
) -> Params:
    dims = (qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
    b, s, _ = x.shape
    dtype = cache_dtype or x.dtype
    _, _, c_kv, k_rope = _mla_qkv(params, x, n_heads, dims)
    cos, sin = rope_tables(jnp.arange(s), qk_rope_head_dim, rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
    cache = mla_cache_init(b, max_len, c_kv.shape[-1], qk_rope_head_dim, dtype)
    cache["c_kv"] = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv.astype(dtype), (0, 0, 0))
    cache["k_rope"] = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope.astype(dtype), (0, 0, 0))
    return cache


def mla_decode(
    params: Params,
    x: jax.Array,
    cache: Params,
    index: jax.Array,
    *,
    n_heads: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    rope_theta: float = 10000.0,
) -> Tuple[jax.Array, Params]:
    dims = (qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
    dn, dr, dv = dims
    dtype = x.dtype
    b = x.shape[0]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, n_heads, dims)
    pos = jnp.asarray(index)[None]
    cos, sin = rope_tables(pos, dr, rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
    cc = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, index, 0))
    cr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, index, 0))
    t = cc.shape[1]
    k_nope, v = _mla_expand_kv(params, cc.astype(dtype), n_heads, dims)
    scale = 1.0 / math.sqrt(dn + dr)
    scores = (
        jnp.einsum("bshn,bthn->bhst", q_nope, k_nope)
        + jnp.einsum("bshr,btr->bhst", q_rope, cr.astype(dtype))
    ).astype(jnp.float32) * scale
    mask = jnp.where(jnp.arange(t) <= index, 0.0, NEG_INF).astype(jnp.float32)
    scores = scores + mask[None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bhst,bthv->bshv", probs, v).reshape(b, 1, n_heads * dv)
    out = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dtype))
    return out, {"c_kv": cc, "k_rope": cr}
