"""Shared neural-net layers (pure-functional JAX; params are pytrees).

Precision policy (TPU-idiomatic): parameters are stored fp32, matmul
activations run bf16, normalization / softmax / router statistics run fp32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# ----------------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale).astype(jnp.float32)


def embed_init(key, vocab: int, d_model: int) -> jax.Array:
    return jax.random.normal(key, (vocab, d_model), dtype=jnp.float32) * 0.02


# ----------------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale) parameterization: zero-init == identity
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------------------------
# Rotary position embedding
# ----------------------------------------------------------------------------

def rope_tables(positions: jax.Array, dim: int, theta: float = 10000.0) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embedding; positions (...,) -> (..., dim//2)."""
    freqs = jnp.exp(
        -jnp.log(theta) * (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., dim//2)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); cos/sin: (..., seq, head_dim//2)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ----------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ----------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff),
        "w_up": dense_init(k2, d_model, d_ff),
        "w_down": dense_init(k3, d_ff, d_model),
    }


def mlp_apply(params: Params, x: jax.Array, activation: str = "silu") -> jax.Array:
    dtype = x.dtype
    gate = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(dtype))
    up = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dtype))
    if activation == "silu":
        act = jax.nn.silu(gate)
    elif activation == "gelu":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        raise ValueError(f"unknown activation {activation}")
    return jnp.einsum("...f,fd->...d", act * up, params["w_down"].astype(dtype))


# ----------------------------------------------------------------------------
# Embedding / LM head
# ----------------------------------------------------------------------------

def embed_lookup(embedding: jax.Array, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return jnp.take(embedding, tokens, axis=0).astype(dtype)


def lm_logits(x: jax.Array, head: jax.Array, softcap: Optional[float] = None,
              divisor: float = 1.0) -> jax.Array:
    """x: (..., D) @ head (D, V) -> fp32 logits, divided by ``divisor``, with
    optional soft-capping."""
    logits = jnp.einsum("...d,dv->...v", x, head.astype(x.dtype)).astype(jnp.float32)
    if divisor != 1.0:
        logits = logits / divisor
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-token loss; logits (..., V) fp32, labels (...) int32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def chunked_cross_entropy(
    x: jax.Array,
    head: jax.Array,
    labels: jax.Array,
    n_chunks: int = 8,
    softcap: Optional[float] = None,
    divisor: float = 1.0,
) -> jax.Array:
    """Cross entropy without materializing full (B, S, V) logits.

    ``lax.scan`` over sequence chunks with a ``jax.checkpoint``-ed body:
    forward and backward both hold one chunk's logits at a time, so peak
    logit memory drops ~n_chunks x.  The baseline path (n_chunks <= 1)
    materializes (B, S, V) logits directly.  (Roofline lowering uses the
    baseline path so XLA's cost model sees every flop — scan bodies are
    costed once; see benchmarks/roofline.py.)
    """
    b, s, d = x.shape
    assert s % n_chunks == 0, (s, n_chunks)
    sc = s // n_chunks
    xc = x.reshape(b, n_chunks, sc, d).swapaxes(0, 1)  # (C, B, s', D)
    lc = labels.reshape(b, n_chunks, sc).swapaxes(0, 1)

    @jax.checkpoint
    def body(total, xs):
        xi, li = xs
        logits = lm_logits(xi, head, softcap, divisor)
        return total + jnp.sum(softmax_cross_entropy(logits, li)), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (b * s)
