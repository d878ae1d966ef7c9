"""Language-model tops: decoder-only LM, VLM (stub frontend), enc-dec.

Public surface (all pure functions of (cfg, params, ...)):

    lm_init(key, cfg)                         -> params
    lm_apply(cfg, params, tokens, **modal)    -> (hidden, aux_loss)
    lm_loss(cfg, params, batch)               -> (loss, metrics)
    prefill(cfg, params, tokens, max_len, **) -> (last_logits, cache)
    decode_step(cfg, params, cache, token)    -> (logits, cache)

Caches mirror the stack structure ({head: [...], groups: {pj: stacked},
tail: [...]}, plus `index`); decode scans groups with (params, cache) as xs
and the refreshed cache as scan output.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.jax_events import scope
from . import attention as attn
from . import rglru as rglru_mod
from . import ssd as ssd_mod
from .layers import (
    chunked_cross_entropy,
    dense_init,
    embed_init,
    embed_lookup,
    lm_logits,
    softmax_cross_entropy,
)
from .transformer import (
    MIXER_SCOPE,
    block_apply,
    layer_theta,
    norm_apply,
    norm_init,
    residual_add,
    stack_init,
)

Params = Dict[str, Any]


def padded_vocab(vocab: int, multiple: int = 256) -> int:
    """Vocab padded for clean TP sharding (GPT-NeoX-style)."""
    return ((vocab + multiple - 1) // multiple) * multiple


# ----------------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------------

def lm_init(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    v = padded_vocab(cfg.vocab)
    cross = cfg.encoder is not None
    params: Params = {
        "embed": embed_init(keys[0], v, cfg.d_model),
        "stack": stack_init(keys[1], cfg, cross=cross),
        "final_norm": norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[2], cfg.d_model, v, scale=0.02)
    if cfg.pos_embed == "learned":
        params["pos_embed"] = (
            jax.random.normal(keys[3], (cfg.max_pos, cfg.d_model), jnp.float32) * 0.01
        )
    if cfg.encoder is not None:
        enc_cfg = cfg.scaled(
            pattern=(("attn_bidir", "mlp"),),
            n_groups=cfg.encoder.n_layers,
            head_pattern=(),
            tail_pattern=(),
            encoder=None,
        )
        params["encoder"] = {
            "stack": stack_init(keys[4], enc_cfg, cross=False),
            "final_norm": norm_init(cfg),
            "pos": jax.random.normal(keys[5], (cfg.encoder.source_len, cfg.d_model), jnp.float32)
            * 0.01,
        }
    return params


# ----------------------------------------------------------------------------
# Forward (training / prefill compute)
# ----------------------------------------------------------------------------

def _embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if cfg.embed_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embed_multiplier, x.dtype)
        return x


def _encoder_out(cfg: ModelConfig, params: Params, frames: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings (B, source_len, D)."""
    from .transformer import stack_apply  # local import to avoid cycle at module load

    enc_cfg = cfg.scaled(
        pattern=(("attn_bidir", "mlp"),),
        n_groups=cfg.encoder.n_layers,
        head_pattern=(),
        tail_pattern=(),
        encoder=None,
    )
    p = params["encoder"]
    x = frames.astype(jnp.bfloat16) + p["pos"][None, : frames.shape[1]].astype(jnp.bfloat16)
    x, _ = stack_apply(enc_cfg, p["stack"], x)
    return norm_apply(cfg, p["final_norm"], x)


def lm_apply(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    *,
    patches: Optional[jax.Array] = None,  # VLM stub embeddings (B, P, D)
    frames: Optional[jax.Array] = None,  # audio stub embeddings (B, T, D)
) -> Tuple[jax.Array, jax.Array]:
    from .transformer import stack_apply

    x = _embed_tokens(cfg, params, tokens)
    if patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"][None, : x.shape[1]].astype(x.dtype)
    enc = _encoder_out(cfg, params, frames) if frames is not None else None
    x, aux = stack_apply(cfg, params["stack"], x, enc_kv_list=enc)
    x = norm_apply(cfg, params["final_norm"], x)
    return x, aux


def _head_matrix(cfg: ModelConfig, params: Params) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def lm_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
    """Next-token cross entropy; batch has `tokens` and `labels` (B, S)."""
    hidden, aux = lm_apply(
        cfg,
        params,
        batch["tokens"],
        patches=batch.get("patches"),
        frames=batch.get("frames"),
    )
    labels = batch["labels"]
    with scope("head_loss"):
        if batch.get("patches") is not None:
            hidden = hidden[:, -labels.shape[1] :]  # loss over text positions only
        head = _head_matrix(cfg, params)
        if cfg.chunked_loss_chunks > 1:
            ce = chunked_cross_entropy(hidden, head, labels, cfg.chunked_loss_chunks, cfg.logit_softcap,
                                       cfg.logits_divisor)
        else:
            logits = lm_logits(hidden, head, cfg.logit_softcap, cfg.logits_divisor)
            ce = jnp.mean(softmax_cross_entropy(logits, labels))
        aux_w = cfg.moe.aux_weight if cfg.moe is not None else 0.0
        loss = ce + aux_w * aux
    return loss, {"ce": ce, "aux": aux}


# ----------------------------------------------------------------------------
# Decode caches
# ----------------------------------------------------------------------------

def _block_cache_init(cfg: ModelConfig, spec, batch: int, max_len: int) -> Params:
    mixer, _ = spec
    hd = cfg.resolved_head_dim
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype)
    if mixer == "attn":
        return attn.gqa_cache_init(batch, max_len, cfg.n_kv_heads, hd, kv_dtype)
    if mixer == "attn_local":
        w = min(cfg.window or max_len, max_len)
        return attn.gqa_cache_init(batch, w, cfg.n_kv_heads, hd, kv_dtype)
    if mixer == "mla":
        m = cfg.mla
        return attn.mla_cache_init(batch, max_len, m.kv_lora_rank, m.qk_rope_head_dim, kv_dtype)
    if mixer == "rglru":
        return rglru_mod.rglru_state_init(batch, cfg.rnn.d_rnn, cfg.rnn.conv_width)
    if mixer == "ssd":
        s = cfg.ssm
        return ssd_mod.ssd_state_init(batch, s.d_inner, s.head_dim, s.d_state, s.n_groups, s.conv_width)
    raise ValueError(mixer)


def _block_cross_cache(cfg: ModelConfig, p: Params, enc: Optional[jax.Array]) -> Params:
    if enc is None or "cross" not in p:
        return {}
    k, v = attn.cross_kv(p["cross"], enc, cfg.n_kv_heads, cfg.resolved_head_dim)
    return {"xk": k, "xv": v}


def _block_prefill(cfg, spec, p, x, max_len, enc):
    """Full-sequence block application + cache construction."""
    mixer, _ = spec
    hd = cfg.resolved_head_dim
    enc_kv = None
    if enc is not None and "cross" in p:
        enc_kv = attn.cross_kv(p["cross"], enc, cfg.n_kv_heads, hd)
    h = norm_apply(cfg, p["norm1"], x)
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype)
    if mixer in ("attn", "attn_local"):
        window = cfg.window if mixer == "attn_local" else None
        cache = attn.gqa_prefill_cache(
            p["mixer"], h, max_len, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=hd, rope_theta=layer_theta(cfg, mixer), window=window,
            cache_dtype=kv_dtype)
    elif mixer == "mla":
        m = cfg.mla
        cache = attn.mla_prefill_cache(
            p["mixer"], h, max_len, n_heads=cfg.n_heads,
            qk_nope_head_dim=m.qk_nope_head_dim, qk_rope_head_dim=m.qk_rope_head_dim,
            v_head_dim=m.v_head_dim, rope_theta=cfg.rope_theta, cache_dtype=kv_dtype)
    elif mixer == "rglru":
        cache = rglru_mod.rglru_prefill_state(p["mixer"], h)
    elif mixer == "ssd":
        s = cfg.ssm
        cache = ssd_mod.ssd_prefill_state(
            p["mixer"], h, d_inner=s.d_inner, head_dim=s.head_dim, d_state=s.d_state,
            n_groups=s.n_groups, chunk=s.chunk)
    else:
        raise ValueError(mixer)
    cache.update(_block_cross_cache(cfg, p, enc))
    x, aux = block_apply(cfg, spec, p, x, enc_kv=enc_kv)
    return x, aux, cache


def _block_decode(cfg, spec, p, cache, x, index):
    mixer, _ = spec
    hd = cfg.resolved_head_dim
    h = norm_apply(cfg, p["norm1"], x)
    cross = {k: cache[k] for k in ("xk", "xv") if k in cache}
    core = {k: v for k, v in cache.items() if k not in ("xk", "xv")}
    if mixer in ("attn", "attn_local"):
        window = cfg.window if mixer == "attn_local" else None
        out, core = attn.gqa_decode(
            p["mixer"], h, core, index, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=hd, rope_theta=layer_theta(cfg, mixer), window=window,
            scale=cfg.attn_scale)
    elif mixer == "mla":
        m = cfg.mla
        out, core = attn.mla_decode(
            p["mixer"], h, core, index, n_heads=cfg.n_heads,
            qk_nope_head_dim=m.qk_nope_head_dim, qk_rope_head_dim=m.qk_rope_head_dim,
            v_head_dim=m.v_head_dim, rope_theta=cfg.rope_theta)
    elif mixer == "rglru":
        out, core = rglru_mod.rglru_decode(p["mixer"], h, core)
    elif mixer == "ssd":
        s = cfg.ssm
        out, core = ssd_mod.ssd_decode(
            p["mixer"], h, core, d_inner=s.d_inner, head_dim=s.head_dim,
            d_state=s.d_state, n_groups=s.n_groups)
    else:
        raise ValueError(mixer)
    x = residual_add(cfg, MIXER_SCOPE.get(mixer), x, out)
    if cross:
        hx = norm_apply(cfg, p["norm_x"], x)
        x = x + attn.cross_attention_apply(
            p["cross"], hx, (cross["xk"], cross["xv"]),
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=hd)
    from .transformer import _ffn_apply

    x, _ = _ffn_apply(cfg, spec, p, x)
    new_cache = dict(core)
    new_cache.update(cross)
    return x, new_cache


# ----------------------------------------------------------------------------
# Prefill / decode drivers
# ----------------------------------------------------------------------------

def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    max_len: int,
    *,
    patches: Optional[jax.Array] = None,
    frames: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """Run the prompt, return (logits at last position fp32, cache)."""
    x = _embed_tokens(cfg, params, tokens)
    if patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"][None, : x.shape[1]].astype(x.dtype)
    enc = _encoder_out(cfg, params, frames) if frames is not None else None

    stack = params["stack"]
    cache: Params = {"head": [], "groups": {}, "tail": []}
    for i, spec in enumerate(cfg.head_pattern):
        x, _, c = _block_prefill(cfg, spec, stack["head"][i], x, max_len, enc)
        cache["head"].append(c)

    if cfg.n_groups > 0:
        def body(x, group_params):
            caches = {}
            for j, spec in enumerate(cfg.pattern):
                x, _, c = _block_prefill(cfg, spec, group_params[f"p{j}"], x, max_len, enc)
                caches[f"p{j}"] = c
            return x, caches

        if cfg.scan_layers:
            x, cache["groups"] = jax.lax.scan(body, x, stack["groups"])
        else:
            per_group = []
            for g in range(cfg.n_groups):
                x, c = body(x, jax.tree.map(lambda t: t[g], stack["groups"]))
                per_group.append(c)
            cache["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)

    for i, spec in enumerate(cfg.tail_pattern):
        x, _, c = _block_prefill(cfg, spec, stack["tail"][i], x, max_len, enc)
        cache["tail"].append(c)

    x = norm_apply(cfg, params["final_norm"], x)
    logits = lm_logits(x[:, -1:], _head_matrix(cfg, params), cfg.logit_softcap, cfg.logits_divisor)
    cache["index"] = jnp.asarray(tokens.shape[1] + (patches.shape[1] if patches is not None else 0), jnp.int32)
    return logits, cache


def cache_init(cfg: ModelConfig, params: Params, batch: int, max_len: int,
               frames: Optional[jax.Array] = None) -> Params:
    """Empty cache (decode-from-scratch; serve_step dry-runs use this)."""
    enc = _encoder_out(cfg, params, frames) if frames is not None else None
    stack = params["stack"]
    cache: Params = {"head": [], "groups": {}, "tail": []}
    for i, spec in enumerate(cfg.head_pattern):
        c = _block_cache_init(cfg, spec, batch, max_len)
        c.update(_block_cross_cache(cfg, stack["head"][i], enc))
        cache["head"].append(c)
    for j, spec in enumerate(cfg.pattern):
        per = []
        for g in range(cfg.n_groups):
            c = _block_cache_init(cfg, spec, batch, max_len)
            if enc is not None:
                pg = jax.tree.map(lambda a: a[g], stack["groups"][f"p{j}"])
                c.update(_block_cross_cache(cfg, pg, enc))
            per.append(c)
        cache["groups"][f"p{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    for i, spec in enumerate(cfg.tail_pattern):
        c = _block_cache_init(cfg, spec, batch, max_len)
        c.update(_block_cross_cache(cfg, stack["tail"][i], enc))
        cache["tail"].append(c)
    cache["index"] = jnp.zeros((), jnp.int32)
    return cache


def decode_step(
    cfg: ModelConfig,
    params: Params,
    cache: Params,
    token: jax.Array,  # (B, 1) int32
) -> Tuple[jax.Array, Params]:
    """One token for every sequence in the batch; returns fp32 logits (B,1,V)."""
    index = cache["index"]
    x = _embed_tokens(cfg, params, token)
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], index, 1, 0)[None].astype(x.dtype)

    stack = params["stack"]
    new_cache: Params = {"head": [], "groups": {}, "tail": []}
    for i, spec in enumerate(cfg.head_pattern):
        x, c = _block_decode(cfg, spec, stack["head"][i], cache["head"][i], x, index)
        new_cache["head"].append(c)

    if cfg.n_groups > 0:
        def body(x, xs):
            group_params, group_cache = xs
            caches = {}
            for j, spec in enumerate(cfg.pattern):
                x, c = _block_decode(cfg, spec, group_params[f"p{j}"], group_cache[f"p{j}"], x, index)
                caches[f"p{j}"] = c
            return x, caches

        if cfg.scan_layers:
            x, new_cache["groups"] = jax.lax.scan(body, x, (stack["groups"], cache["groups"]))
        else:
            per_group = []
            for g in range(cfg.n_groups):
                x, c = body(
                    x,
                    jax.tree.map(lambda t: t[g], (stack["groups"], cache["groups"])),
                )
                per_group.append(c)
            new_cache["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)

    for i, spec in enumerate(cfg.tail_pattern):
        x, c = _block_decode(cfg, spec, stack["tail"][i], cache["tail"][i], x, index)
        new_cache["tail"].append(c)

    x = norm_apply(cfg, params["final_norm"], x)
    logits = lm_logits(x, _head_matrix(cfg, params), cfg.logit_softcap, cfg.logits_divisor)
    new_cache["index"] = index + 1
    return logits, new_cache
