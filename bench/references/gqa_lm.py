"""Plain float32 decoder-only transformer with grouped-query attention,
rotary embedding and a SwiGLU MLP (the Mistral family), in the parameter
layout of the program's ``("attn", "mlp")`` blocks.

Attention is the textbook softmax(q k^T / sqrt(d)) v with a causal mask,
taken over blocks of 512 queries so that one row's scores fit.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .common import cross_entropy_sum, mm, rms_norm, rotate_half_rope

Q_BLOCK = 512


def _attention(model, p, u, mode: str):
    s = u.shape[0]
    h, k, d = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    pos = jnp.arange(s)
    q = rotate_half_rope(mm("sd,de->se", u, p["wq"], mode).reshape(s, h, d), pos, model["rope_theta"])
    kk = rotate_half_rope(mm("sd,de->se", u, p["wk"], mode).reshape(s, k, d), pos, model["rope_theta"])
    vv = mm("sd,de->se", u, p["wv"], mode).reshape(s, k, d)
    kk = jnp.repeat(kk, h // k, axis=1)  # query head i reads kv head i // (h/k)
    vv = jnp.repeat(vv, h // k, axis=1)
    @jax.checkpoint  # the backward pass holds one block's scores at a time
    def block(qb, q0):
        scores = mm("qhd,khd->hqk", qb, kk, mode) / math.sqrt(d)
        qpos = q0 + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.where((pos[None, :] <= qpos)[None], scores, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vv, mode)

    outs = [block(q[q0 : q0 + Q_BLOCK], q0) for q0 in range(0, s, Q_BLOCK)]
    out = jnp.concatenate(outs, axis=0).reshape(s, h * d)
    return mm("se,ed->sd", out, p["wo"], mode)


def _block(model, p, x, mode: str):
    eps = model["norm_eps"]
    x = x + _attention(model, p["mixer"], rms_norm(x, p["norm1"]["scale"], eps), mode)
    u = rms_norm(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    gate = mm("sd,df->sf", u, f["w_gate"], mode)
    up = mm("sd,df->sf", u, f["w_up"], mode)
    return x + mm("sf,fd->sd", jax.nn.silu(gate) * up, f["w_down"], mode)


def hidden(model: Dict[str, Any], params, tokens: jax.Array, mode: str) -> jax.Array:
    if [tuple(s) for s in model["pattern"]] != [("attn", "mlp")]:
        raise ValueError("gqa_lm covers a stack of ('attn', 'mlp') blocks")
    x = params["embed"][tokens].astype(jnp.float32)

    @jax.checkpoint
    def layer(x, p):
        return _block(model, p, x, mode), None

    x, _ = jax.lax.scan(layer, x, params["stack"]["groups"]["p0"])
    return rms_norm(x, params["final_norm"]["scale"], model["norm_eps"])


def head(model, params) -> jax.Array:
    return params["embed"].T if model["tie_embeddings"] else params["lm_head"]


def logits(model, params, tokens, mode: str) -> jax.Array:
    return mm("sd,dv->sv", hidden(model, params, tokens, mode), head(model, params), mode)


def row_loss(model, params, tokens, labels, mode: str) -> jax.Array:
    return cross_entropy_sum(logits(model, params, tokens, mode), labels)
