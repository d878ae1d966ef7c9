"""Plain float32 Mamba-2 language model (arXiv:2405.21060), in the
parameter layout of the program's ``ssd`` blocks.

Each layer: x += out_proj(gated_rmsnorm(SSD(conv(in_proj(rmsnorm(x)))))).
The SSD is written in its quadratic "attention" form over the whole
sequence, y_t = sum_{s<=t} (C_t . B_s) exp(sum_{k=s+1..t} dt_k A) dt_s x_s
+ D x_t, which is independent of the chunked scan the program runs. The
head is tied to the embedding when the model says so.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .common import cross_entropy_sum, mm, rms_norm


def _conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Causal depthwise convolution of width w.shape[0]; xbc (S, C)."""
    width = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    s = xbc.shape[0]
    out = sum(xp[i : i + s] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def ssd_quadratic(x, dt, a, bm, cm, mode: str):
    """x (S,H,P), dt (S,H), a (H,), bm/cm (S,G,N) -> y (S,H,P)."""
    s, h, p = x.shape
    g = bm.shape[1]
    rep = h // g
    la = dt * a[None, :]  # (S,H)
    cs = jnp.cumsum(la, axis=0)
    diff = cs[:, None, :] - cs[None, :, :]  # (t, s, H): sum_{k=s+1..t}
    causal = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = mm("tgn,sgn->gts", cm, bm, mode)  # (G,t,s)
    cb = jnp.repeat(cb, rep, axis=0)  # (H,t,s)
    w = cb * decay.transpose(2, 0, 1)  # (H,t,s)
    return mm("hts,shp->thp", w, x * dt[:, :, None], mode)


def _ssd_layer(model, p, x, mode: str):
    s_cfg = model["ssm"]
    di, n, g, hd = s_cfg["d_inner"], s_cfg["d_state"], s_cfg["n_groups"], s_cfg["head_dim"]
    h = di // hd
    eps = model["norm_eps"]
    u = rms_norm(x, p["norm1"]["scale"], eps)
    mx = p["mixer"]
    zxbcdt = mm("sd,de->se", u, mx["in_proj"], mode)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di : 2 * di + 2 * g * n]
    dt = zxbcdt[:, 2 * di + 2 * g * n :]
    xbc = _conv(xbc, mx["conv_w"], mx["conv_b"])
    xs = xbc[:, :di].reshape(-1, h, hd)
    bm = xbc[:, di : di + g * n].reshape(-1, g, n)
    cm = xbc[:, di + g * n :].reshape(-1, g, n)
    dt = jax.nn.softplus(dt + mx["dt_bias"])
    a = -jnp.exp(mx["a_log"])
    y = ssd_quadratic(xs, dt, a, bm, cm, mode) + xs * mx["d_skip"][None, :, None]
    y = y.reshape(-1, di) * jax.nn.silu(z)
    y = rms_norm(y, mx["norm"], eps)
    return x + mm("sn,nd->sd", y, mx["out_proj"], mode)


def hidden(model: Dict[str, Any], params, tokens: jax.Array, mode: str) -> jax.Array:
    """Final-normed hidden states (S, D) of one row of tokens."""
    if [tuple(s) for s in model["pattern"]] != [("ssd", "none")]:
        raise ValueError("ssm_lm covers a stack of ('ssd', 'none') blocks")
    x = params["embed"][tokens].astype(jnp.float32)

    @jax.checkpoint
    def layer(x, p):
        return _ssd_layer(model, p, x, mode), None

    x, _ = jax.lax.scan(layer, x, params["stack"]["groups"]["p0"])
    return rms_norm(x, params["final_norm"]["scale"], model["norm_eps"])


def head(model, params) -> jax.Array:
    return params["embed"].T if model["tie_embeddings"] else params["lm_head"]


def logits(model, params, tokens, mode: str) -> jax.Array:
    return mm("sd,dv->sv", hidden(model, params, tokens, mode), head(model, params), mode)


def row_loss(model, params, tokens, labels, mode: str) -> jax.Array:
    return cross_entropy_sum(logits(model, params, tokens, mode), labels)
