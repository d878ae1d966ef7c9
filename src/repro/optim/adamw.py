"""AdamW with decoupled weight decay + gradient clipping (pure pytree impl)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.jax_events import scope

Params = Any
OptState = Dict[str, Any]


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip_norm: float = 1.0
    schedule: Optional[Callable[[jax.Array], jax.Array]] = None  # step -> lr scale


def init(params: Params) -> OptState:
    zeros = lambda p: jnp.zeros_like(p)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: Params) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def update(
    cfg: AdamWConfig,
    grads: Params,
    state: OptState,
    params: Params,
) -> Tuple[Params, OptState, Dict[str, jax.Array]]:
    """Returns (new_params, new_state, stats)."""
    with scope("optimizer"):
        return _update(cfg, grads, state, params)


def _update(cfg: AdamWConfig, grads: Params, state: OptState, params: Params):
    count = state["count"] + 1
    gnorm = global_norm(grads)
    if cfg.grad_clip_norm > 0:
        scale = jnp.minimum(1.0, cfg.grad_clip_norm / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)

    lr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.schedule is not None:
        lr = lr * cfg.schedule(count)

    b1, b2 = cfg.b1, cfg.b2
    c = count.astype(jnp.float32)
    bias1 = 1.0 - b1**c
    bias2 = 1.0 - b2**c

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / bias1
        vhat = v / bias2
        step = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay > 0:
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

    flat_p, tree = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    new_p, new_m, new_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        np_, nm, nv = upd(p, g, m, v)
        new_p.append(np_)
        new_m.append(nm)
        new_v.append(nv)
    return (
        jax.tree.unflatten(tree, new_p),
        {"m": jax.tree.unflatten(tree, new_m), "v": jax.tree.unflatten(tree, new_v), "count": count},
        {"grad_norm": gnorm, "lr": lr},
    )


def cosine_schedule(warmup: int, total: int, min_scale: float = 0.1) -> Callable:
    def fn(step):
        step = step.astype(jnp.float32)
        warm = jnp.minimum(step / max(warmup, 1), 1.0)
        progress = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = min_scale + (1 - min_scale) * 0.5 * (1 + jnp.cos(jnp.pi * progress))
        return warm * cos

    return fn
