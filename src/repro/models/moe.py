"""Mixture-of-Experts FFN — DeepSeekMoE-style shared + fine-grained routed
experts (arXiv:2401.06066 / 2405.04434), GShard capacity-based dispatch.

TPU adaptation: routing materializes dispatch/combine one-hots of shape
(groups, S, E, C) and the expert GEMMs run as einsums with the expert axis
first — the canonical pjit-friendly formulation (the expert axis shards on
the `model` mesh axis = expert parallelism; XLA inserts the all-to-alls).
Capacity C = S * top_k / E * capacity_factor, overflow tokens are
dropped.  Token groups bound the dispatch tensor size:
S*E*C grows ~ S^2 * top_k * cf, so callers group long sequences.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, mlp_apply, mlp_init

Params = Dict[str, Any]


def moe_init(
    key,
    d_model: int,
    d_ff_expert: int,
    n_experts: int,
    n_shared: int = 0,
    d_ff_shared: Optional[int] = None,
) -> Params:
    kr, ke, ks = jax.random.split(key, 3)
    # routed experts: stacked along a leading expert axis (shards on `model`)
    e_keys = jax.random.split(ke, 3)
    params: Params = {
        "router": dense_init(kr, d_model, n_experts, scale=0.02),
        "experts": {
            "w_gate": _stack_init(e_keys[0], n_experts, d_model, d_ff_expert),
            "w_up": _stack_init(e_keys[1], n_experts, d_model, d_ff_expert),
            "w_down": _stack_init(e_keys[2], n_experts, d_ff_expert, d_model),
        },
    }
    if n_shared > 0:
        params["shared"] = mlp_init(ks, d_model, d_ff_shared or (d_ff_expert * n_shared))
    return params


def _stack_init(key, n: int, d_in: int, d_out: int) -> jax.Array:
    keys = jax.random.split(key, n)
    return jnp.stack([dense_init(k, d_in, d_out) for k in keys])


def _capacity(tokens_per_group: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(tokens_per_group * top_k * factor / n_experts)
    return max(c, top_k)


def moe_apply(
    params: Params,
    x: jax.Array,  # (B, S, D)
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    group_size: int = 4096,
    router_noise: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_loss scalar)."""
    dtype = x.dtype
    b, s, d = x.shape
    tokens = b * s
    gs = min(group_size, tokens)
    assert tokens % gs == 0, (tokens, gs)
    g = tokens // gs
    xg = x.reshape(g, gs, d)

    # -- routing (fp32) -----------------------------------------------------
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), params["router"])
    if router_noise > 0.0 and rng is not None:
        logits = logits + router_noise * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)  # (g, gs, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)  # (g, gs, k)
    # DeepSeek normalizes the top-k gate values to sum to 1
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # -- load-balancing auxiliary loss (Switch/GShard form) ------------------
    me = jnp.mean(probs, axis=1)  # (g, E) mean router prob
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, n_experts, dtype=jnp.float32), axis=2), axis=1
    ) / top_k  # (g, E) fraction of tokens per expert
    aux_loss = jnp.mean(jnp.sum(me * ce, axis=-1)) * n_experts

    # -- capacity assignment --------------------------------------------------
    c = _capacity(gs, n_experts, top_k, capacity_factor)
    sel_onehot = jax.nn.one_hot(expert_ids, n_experts, dtype=jnp.float32)  # (g, gs, k, E)
    # position of each (token, k) within its expert queue, in token order with
    # priority to lower k (primary routes beat secondary on overflow)
    flat = sel_onehot.transpose(0, 2, 1, 3).reshape(g, top_k * gs, n_experts)
    pos_flat = jnp.cumsum(flat, axis=1) - flat  # (g, k*gs, E)
    pos = pos_flat.reshape(g, top_k, gs, n_experts).transpose(0, 2, 1, 3)  # (g, gs, k, E)
    pos = jnp.sum(pos * sel_onehot, axis=-1).astype(jnp.int32)  # (g, gs, k)
    keep = pos < c
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # -- dispatch / combine one-hots -----------------------------------------
    pos_onehot = jax.nn.one_hot(pos, c, dtype=jnp.float32)  # (g, gs, k, C)
    # (g, gs, E, C) = sum_k sel(k) x pos(k)
    dispatch = jnp.einsum("gske,gskc->gsec", sel_onehot, pos_onehot * keep[..., None].astype(jnp.float32))
    combine = jnp.einsum("gske,gskc->gsec", sel_onehot * gate_vals[..., None], pos_onehot)

    # -- expert computation (expert axis leads; shards on `model`) -----------
    ex_in = jnp.einsum("gsec,gsd->egcd", dispatch.astype(dtype), xg)  # (E, g, C, D)
    w = params["experts"]
    gate = jnp.einsum("egcd,edf->egcf", ex_in, w["w_gate"].astype(dtype))
    up = jnp.einsum("egcd,edf->egcf", ex_in, w["w_up"].astype(dtype))
    h = jax.nn.silu(gate) * up
    ex_out = jnp.einsum("egcf,efd->egcd", h, w["w_down"].astype(dtype))
    out = jnp.einsum("gsec,egcd->gsd", combine.astype(dtype), ex_out)

    # -- shared experts (always-on dense path, DeepSeekMoE) -------------------
    if "shared" in params:
        out = out + mlp_apply(params["shared"], xg)
    return out.reshape(b, s, d), aux_loss.astype(jnp.float32)
