"""Block assembly: pattern-based stacks with group-scan, LM / enc-dec tops.

Design notes
------------
* Layers are grouped by ``cfg.pattern`` and scanned with ``jax.lax.scan``
  over stacked parameters — HLO size stays O(pattern) not O(depth), which
  keeps 512-device lowering fast for 60-layer models.
* Heterogeneous stacks (gemma3's 5 local : 1 global, recurrentgemma's
  R,R,A) are expressed inside the pattern, so the scan body stays static.
* ``remat`` wraps the scanned group body in ``jax.checkpoint``, or each
  block of it where the pattern has several.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.jax_events import scope
from . import attention as attn
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssd as ssd_mod
from .layers import (
    chunked_cross_entropy,
    dense_init,
    embed_init,
    embed_lookup,
    layer_norm,
    lm_logits,
    mlp_apply,
    mlp_init,
    rms_norm,
    softmax_cross_entropy,
)

Params = Dict[str, Any]

# ----------------------------------------------------------------------------
# Norm helpers (rms for llama/gemma-likes, layer for whisper)
# ----------------------------------------------------------------------------

def norm_init(cfg: ModelConfig) -> Params:
    if cfg.norm_type == "layer":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}


def norm_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    with scope("norm"):
        if cfg.norm_type == "layer":
            return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
        return rms_norm(x, p["scale"], cfg.norm_eps)


# ----------------------------------------------------------------------------
# Plain (non-gated) MLP for whisper
# ----------------------------------------------------------------------------

def plain_mlp_init(key, d_model: int, d_ff: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {"w1": dense_init(k1, d_model, d_ff), "b1": jnp.zeros((d_ff,), jnp.float32),
            "w2": dense_init(k2, d_ff, d_model), "b2": jnp.zeros((d_model,), jnp.float32)}


def plain_mlp_apply(p: Params, x: jax.Array) -> jax.Array:
    dtype = x.dtype
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["w1"].astype(dtype)) + p["b1"].astype(dtype))
    return jnp.einsum("...f,fd->...d", h, p["w2"].astype(dtype)) + p["b2"].astype(dtype)


# ----------------------------------------------------------------------------
# Blocks
# ----------------------------------------------------------------------------

def _mixer_init(key, cfg: ModelConfig, mixer: str) -> Params:
    hd = cfg.resolved_head_dim
    if mixer in ("attn", "attn_local", "attn_bidir"):
        return attn.gqa_init(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                             qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)
    if mixer == "mla":
        m = cfg.mla
        return attn.mla_init(key, cfg.d_model, cfg.n_heads, m.q_lora_rank, m.kv_lora_rank,
                             m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim)
    if mixer == "rglru":
        return rglru_mod.rglru_init(key, cfg.d_model, cfg.rnn.d_rnn, cfg.rnn.conv_width)
    if mixer == "ssd":
        s = cfg.ssm
        return ssd_mod.ssd_init(key, cfg.d_model, s.d_inner, s.head_dim, s.d_state,
                                s.n_groups, s.conv_width)
    raise ValueError(f"unknown mixer {mixer}")


def _ffn_init(key, cfg: ModelConfig, ffn: str) -> Optional[Params]:
    if ffn == "none":
        return None
    if ffn == "mlp":
        if cfg.gated_mlp:
            return mlp_init(key, cfg.d_model, cfg.d_ff)
        return plain_mlp_init(key, cfg.d_model, cfg.d_ff)
    if ffn == "moe":
        m = cfg.moe
        return moe_mod.moe_init(key, cfg.d_model, m.d_ff_expert, m.n_experts,
                                m.n_shared, m.d_ff_shared)
    raise ValueError(f"unknown ffn {ffn}")


def block_init(key, cfg: ModelConfig, spec: Tuple[str, str], cross: bool = False) -> Params:
    mixer, ffn = spec
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {"norm1": norm_init(cfg), "mixer": _mixer_init(k1, cfg, mixer)}
    if cross:
        p["norm_x"] = norm_init(cfg)
        p["cross"] = attn.gqa_init(k3, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias)
    f = _ffn_init(k2, cfg, ffn)
    if f is not None:
        p["norm2"] = norm_init(cfg)
        p["ffn"] = f
    return p


def layer_theta(cfg: ModelConfig, mixer: str) -> Optional[float]:
    """The rotary base of an attention layer; None where it has no rotary."""
    if cfg.pos_embed == "none":
        return None
    if mixer == "attn_local" and cfg.rope_theta_local is not None:
        return cfg.rope_theta_local
    return cfg.rope_theta


# The scope a mixer's output is scaled in before the residual add.
MIXER_SCOPE = {"attn": "attn_proj", "attn_local": "attn_proj", "attn_bidir": "attn_proj", "ssd": "ssd_proj"}


def residual_add(cfg: ModelConfig, scope_name: Optional[str], x: jax.Array, out: jax.Array) -> jax.Array:
    """x + out, the sublayer's output first scaled by ``residual_multiplier``
    inside the sublayer's own scope."""
    if cfg.residual_multiplier != 1.0:
        with scope(scope_name or "layer_stack"):
            out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return x + out


def _ffn_apply(cfg: ModelConfig, spec: Tuple[str, str], p: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Returns (out, aux_loss)."""
    _, ffn = spec
    zero = jnp.zeros((), jnp.float32)
    if ffn == "none":
        return x, zero
    h = norm_apply(cfg, p["norm2"], x)
    if ffn == "mlp":
        with scope("mlp"):
            if cfg.gated_mlp:
                out = mlp_apply(p["ffn"], h, cfg.activation)
            else:
                out = plain_mlp_apply(p["ffn"], h)
        return residual_add(cfg, "mlp", x, out), zero
    m = cfg.moe
    out, aux = moe_mod.moe_apply(p["ffn"], h, n_experts=m.n_experts, top_k=m.top_k,
                                 capacity_factor=m.capacity_factor, group_size=m.group_size)
    return residual_add(cfg, None, x, out), aux


def block_apply(
    cfg: ModelConfig,
    spec: Tuple[str, str],
    p: Params,
    x: jax.Array,
    *,
    enc_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence block (train / prefill compute). Returns (x, aux)."""
    mixer, _ = spec
    hd = cfg.resolved_head_dim
    # Mosaic kernels cannot be partitioned automatically: a step sharded
    # over a mesh keeps the XLA scans.
    scan_kernels = cfg.use_scan_kernels and cfg.act_pspec is None
    h = norm_apply(cfg, p["norm1"], x)
    if mixer in ("attn", "attn_local", "attn_bidir"):
        window = cfg.window if mixer == "attn_local" else None
        out = attn.gqa_apply(
            p["mixer"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=hd,
            rope_theta=layer_theta(cfg, mixer), causal=(mixer != "attn_bidir"),
            window=window, positions=positions, chunk_q=cfg.attn_chunk_q,
            use_flash_kernel=cfg.use_flash_kernel, act_pspec=cfg.act_pspec,
            scale=cfg.attn_scale)
    elif mixer == "mla":
        m = cfg.mla
        out = attn.mla_apply(p["mixer"], h, n_heads=cfg.n_heads,
                             qk_nope_head_dim=m.qk_nope_head_dim,
                             qk_rope_head_dim=m.qk_rope_head_dim,
                             v_head_dim=m.v_head_dim, rope_theta=cfg.rope_theta,
                             positions=positions, chunk_q=cfg.attn_chunk_q,
                             act_pspec=cfg.act_pspec)
    elif mixer == "rglru":
        out = rglru_mod.rglru_apply(p["mixer"], h, use_kernel=scan_kernels)
    elif mixer == "ssd":
        s = cfg.ssm
        out = ssd_mod.ssd_apply(p["mixer"], h, d_inner=s.d_inner, head_dim=s.head_dim,
                                d_state=s.d_state, n_groups=s.n_groups, chunk=s.chunk,
                                use_kernel=scan_kernels)
    else:
        raise ValueError(mixer)
    x = residual_add(cfg, MIXER_SCOPE.get(mixer), x, out)
    if "cross" in p and enc_kv is not None:
        hx = norm_apply(cfg, p["norm_x"], x)
        x = x + attn.cross_attention_apply(p["cross"], hx, enc_kv, n_heads=cfg.n_heads,
                                           n_kv_heads=cfg.n_kv_heads, head_dim=hd)
    return _ffn_apply(cfg, spec, p, x)


# ----------------------------------------------------------------------------
# Stacks (head + scanned groups + tail)
# ----------------------------------------------------------------------------

def _stack_trees(trees: List[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def stack_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    keys = jax.random.split(key, 3)
    head = [block_init(jax.random.fold_in(keys[0], i), cfg, spec, cross)
            for i, spec in enumerate(cfg.head_pattern)]
    groups: Dict[str, Params] = {}
    for j, spec in enumerate(cfg.pattern):
        per_group = [block_init(jax.random.fold_in(keys[1], g * 131 + j), cfg, spec, cross)
                     for g in range(cfg.n_groups)]
        groups[f"p{j}"] = _stack_trees(per_group)
    tail = [block_init(jax.random.fold_in(keys[2], i), cfg, spec, cross)
            for i, spec in enumerate(cfg.tail_pattern)]
    return {"head": head, "groups": groups, "tail": tail}


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def constrain_acts(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Megatron-SP residual-stream constraint: (batch, seq, d) sharded
    (batch_axes, seq_axes, None).  The scan carry saved for backward is the
    sharded tensor, cutting per-device activation memory by the model-axis
    width; XLA inserts the all-gather / reduce-scatter pair around each
    block's TP matmuls (standard sequence parallelism)."""
    if cfg.act_pspec is None or x.ndim != 3:
        return x
    batch_axes, seq_axes = cfg.act_pspec
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, seq_axes, None)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, TypeError):  # no mesh context (CPU smoke paths)
        return x


def stack_apply(
    cfg: ModelConfig,
    params: Params,
    x: jax.Array,
    *,
    enc_kv_list: Optional[List] = None,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Apply the whole stack; returns (x, total_aux_loss).

    ``enc_kv_list``: for enc-dec decoders, per-position cross K/V. The scanned
    groups receive stacked cross K/V is not supported — whisper's uniform
    decoder computes cross K/V inside the block from a closed-over encoder
    output instead (see ``encdec_apply``)."""
    aux = jnp.zeros((), jnp.float32)
    enc_out = enc_kv_list  # only used via closure in group body for enc-dec
    x = constrain_acts(cfg, x)

    for i, spec in enumerate(cfg.head_pattern):
        x, a = block_apply(cfg, spec, params["head"][i], x,
                           enc_kv=_cross_kv_for(cfg, params["head"][i], enc_out),
                           positions=positions)
        x = constrain_acts(cfg, x)
        aux = aux + a

    if cfg.n_groups > 0:
        # A group of several blocks is recomputed a block at a time, so the
        # backward pass holds one block's intermediates, not the group's.
        per_block = len(cfg.pattern) > 1

        def group_body(carry, group_params):
            x, aux = carry
            for j, spec in enumerate(cfg.pattern):
                p = group_params[f"p{j}"]
                apply = partial(block_apply, cfg, spec, enc_kv=_cross_kv_for(cfg, p, enc_out),
                                positions=positions)
                if per_block:
                    apply = _maybe_remat(cfg, apply)
                x, a = apply(p, x)
                x = constrain_acts(cfg, x)
                aux = aux + a
            return (x, aux), None

        body = group_body if per_block else _maybe_remat(cfg, group_body)
        with scope("layer_stack"):
            if cfg.scan_layers:
                (x, aux), _ = jax.lax.scan(body, (x, aux), params["groups"])
            else:  # unrolled: every layer visible to the XLA cost model
                for g in range(cfg.n_groups):
                    (x, aux), _ = body((x, aux), jax.tree.map(lambda t: t[g], params["groups"]))

    for i, spec in enumerate(cfg.tail_pattern):
        x, a = block_apply(cfg, spec, params["tail"][i], x,
                           enc_kv=_cross_kv_for(cfg, params["tail"][i], enc_out),
                           positions=positions)
        x = constrain_acts(cfg, x)
        aux = aux + a
    return x, aux


def _cross_kv_for(cfg: ModelConfig, block_params: Params, enc_out) -> Optional[Tuple]:
    if enc_out is None or "cross" not in block_params:
        return None
    return attn.cross_kv(block_params["cross"], enc_out, cfg.n_kv_heads, cfg.resolved_head_dim)
