"""Plain float32 Granite 4.0-H language model (``granitemoehybrid`` with no
experts: granite-4.0-h-micro), in the parameter layout of the program's
``("ssd", "mlp")`` and ``("attn", "mlp")`` blocks.

Each layer, with r the residual multiplier:

    x <- x + r * mixer(rmsnorm(x))
    x <- x + r * SwiGLU(rmsnorm(x))

The mixer is Mamba-2 (in_proj to z, x B C and dt; causal depthwise conv
and SiLU over x B C; the SSD; y * silu(z) through an RMS norm over d_inner;
out_proj) or causal grouped-query attention with no position embedding
and the configured softmax scale. The embedding is multiplied by the
embedding multiplier, the last hidden state goes through a final RMS norm
and the tied head, and the logits are divided by the logits divisor.

The SSD is taken in its quadratic form,
y_t = sum_{s<=t} (C_t . B_s) exp(sum_{k=s+1..t} dt_k A) dt_s x_s + D x_t,
which is independent of the chunked scan the program runs. It is computed
in a scan over blocks of 64 queries against every key, masked above the
diagonal, so that one block's decay is (heads, 64, keys) and the gradients
of the keys' operands add up in the scan's carry; attention is blocked the
same way, 256 queries at a time, and the MLP and the loss 2048 rows at a
time. The blocks above the diagonal cost work (twice the causal half) and
no memory. Every block, and every layer, is recomputed in the backward
pass. The block sizes keep one row's gradient at 16384 tokens within what
a 16 GB chip has left beside the parameters and the gradient being summed.

Departures from the published model, each also the program's:
- norm weights are stored as ``1 + scale`` (zero-initialised scale);
- the MLP keeps its gate and up projections as two matrices, where the
  published ``input_linear`` holds them as the two halves of one;
- the vocabulary is the slice the configuration holds.
``pos_embed: "rope"`` adds rotary embedding to the attention layers; the
published model has none, and the tests use it as a negative control.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .common import cross_entropy_sum, mm, rms_norm, rotate_half_rope
from .ssm_lm import _conv

SSD_Q_BLOCK = 64
ATTN_Q_BLOCK = 256
ROW_BLOCK = 2048


def _by_rows(fn, *xs, block: int):
    """``fn(r0, *blocks)`` over blocks of ``block`` rows of ``xs``, r0 the
    block's first row, in one ``lax.scan`` whose body the backward pass
    recomputes; the results stacked by block."""
    s = xs[0].shape[0]
    if s % block:
        raise ValueError(f"{s} rows do not split into blocks of {block}")
    blocks = [x.reshape(s // block, block, *x.shape[1:]) for x in xs]
    _, out = jax.lax.scan(jax.checkpoint(lambda c, b: (c, fn(*b))), None,
                          (jnp.arange(0, s, block), *blocks))
    return out


def ssd_blocked(x, dt, a, bm, cm, mode: str, block: int = SSD_Q_BLOCK):
    """x (S,H,P), dt (S,H), a (H,), bm/cm (S,G,N) -> y (S,H,P) without the
    D skip, in blocks of ``block`` queries."""
    s, h, _ = x.shape
    block = min(block, s)
    rep = h // bm.shape[1]
    cs = jnp.cumsum(dt * a[None, :], axis=0)  # (S,H)
    xdt = x * dt[:, :, None]

    def qblock(q0, cq, cmq):
        diff = cq[:, None, :] - cs[None, :, :]  # (t, s, H): sum_{k=s+1..t}
        causal = (q0 + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
        cb = jnp.repeat(mm("tgn,sgn->gts", cmq, bm, mode), rep, axis=0)
        return mm("hts,shp->thp", cb * decay.transpose(2, 0, 1), xdt, mode)

    return _by_rows(qblock, cs, cm, block=block).reshape(x.shape)


def _mamba(model, p, u, mode: str):
    s_cfg = model["ssm"]
    di, n, g, hd = s_cfg["d_inner"], s_cfg["d_state"], s_cfg["n_groups"], s_cfg["head_dim"]
    h = di // hd
    w = p["in_proj"]  # columns z | x B C | dt, each projected on its own
    z = mm("sd,de->se", u, w[:, :di], mode)
    xbc = _conv(mm("sd,de->se", u, w[:, di : 2 * di + 2 * g * n], mode), p["conv_w"], p["conv_b"])
    dt = jax.nn.softplus(mm("sd,de->se", u, w[:, 2 * di + 2 * g * n :], mode) + p["dt_bias"])
    xs = xbc[:, :di].reshape(-1, h, hd)
    bm = xbc[:, di : di + g * n].reshape(-1, g, n)
    cm = xbc[:, di + g * n :].reshape(-1, g, n)
    a = -jnp.exp(p["a_log"])
    y = ssd_blocked(xs, dt, a, bm, cm, mode) + xs * p["d_skip"][None, :, None]
    y = rms_norm(y.reshape(-1, di) * jax.nn.silu(z), p["norm"], model["norm_eps"])
    return mm("sn,nd->sd", y, p["out_proj"], mode)


def _attention(model, p, u, mode: str):
    s = u.shape[0]
    h, k, d = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    q = mm("sd,de->se", u, p["wq"], mode).reshape(s, h, d)
    kk = mm("sd,de->se", u, p["wk"], mode).reshape(s, k, d)
    vv = mm("sd,de->se", u, p["wv"], mode).reshape(s, k, d)
    if model["pos_embed"] == "rope":
        pos = jnp.arange(s)
        q = rotate_half_rope(q, pos, model["rope_theta"])
        kk = rotate_half_rope(kk, pos, model["rope_theta"])
    elif model["pos_embed"] != "none":
        raise ValueError(f"granite_hybrid_lm: no position embedding {model['pos_embed']!r}")
    kk = jnp.repeat(kk, h // k, axis=1)  # query head i reads kv head i // (h/k)
    vv = jnp.repeat(vv, h // k, axis=1)
    block = min(ATTN_Q_BLOCK, s)

    def qblock(q0, qb):
        scores = mm("qhd,khd->hqk", qb, kk, mode) * model["attn_scale"]
        causal = (q0 + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vv, mode)

    out = _by_rows(qblock, q, block=block).reshape(s, h * d)
    return mm("se,ed->sd", out, p["wo"], mode)


def _mlp(p, u, mode: str):
    def rows(_, u):
        gate = mm("sd,df->sf", u, p["w_gate"], mode)
        up = mm("sd,df->sf", u, p["w_up"], mode)
        return mm("sf,fd->sd", jax.nn.silu(gate) * up, p["w_down"], mode)

    return _by_rows(rows, u, block=min(ROW_BLOCK, u.shape[0])).reshape(u.shape)


MIXERS = {"ssd": _mamba, "attn": _attention}


def hidden(model: Dict[str, Any], params, tokens: jax.Array, mode: str) -> jax.Array:
    """Final-normed hidden states (S, D) of one row of tokens."""
    specs = [tuple(s) for s in model["pattern"]]
    if any(mixer not in MIXERS or ffn != "mlp" for mixer, ffn in specs):
        raise ValueError(f"granite_hybrid_lm covers ('ssd' | 'attn', 'mlp') blocks, not {specs}")
    eps, r = model["norm_eps"], model["residual_multiplier"]
    x = params["embed"][tokens].astype(jnp.float32) * model["embed_multiplier"]

    @partial(jax.checkpoint, static_argnums=(0,))  # recomputed a layer at a time
    def layer(mixer, p, x):
        x = x + r * MIXERS[mixer](model, p["mixer"], rms_norm(x, p["norm1"]["scale"], eps), mode)
        return x + r * _mlp(p["ffn"], rms_norm(x, p["norm2"]["scale"], eps), mode)

    def group(x, group_params):
        for j, (mixer, _) in enumerate(specs):
            x = layer(mixer, group_params[f"p{j}"], x)
        return x, None

    x, _ = jax.lax.scan(group, x, params["stack"]["groups"])
    return rms_norm(x, params["final_norm"]["scale"], model["norm_eps"])


def _head(model, params):
    return params["embed"].T if model["tie_embeddings"] else params["lm_head"]


def logits(model, params, tokens, mode: str) -> jax.Array:
    h = hidden(model, params, tokens, mode)
    return mm("sd,dv->sv", h, _head(model, params), mode) / model["logits_divisor"]


def row_loss(model, params, tokens, labels, mode: str) -> jax.Array:
    """Sum of the row's cross entropies, the logits made a block of rows at
    a time."""
    head = _head(model, params)

    def rows(_, h, labels):
        logits = mm("sd,dv->sv", h, head, mode) / model["logits_divisor"]
        return cross_entropy_sum(logits, labels)

    block = min(ROW_BLOCK, tokens.shape[0])
    return jnp.sum(_by_rows(rows, hidden(model, params, tokens, mode), labels, block=block))
