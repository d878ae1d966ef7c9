"""Seeded weights in the program's parameter layout, made in one jitted call.

The layout (the pytree of shapes) comes from the program; the values come
from here, so the program and the reference are fed the same weights and
neither made them. Scales follow the published inits: 0.02 for the
embedding and head, 1/sqrt(fan_in) for projections, zero-centred norm
scales (the program's norms multiply by 1 + scale), Mamba-2's A, dt and D
inits for the SSD mixer.
"""

from __future__ import annotations

import math
from typing import Any, Callable, List

import jax
import jax.numpy as jnp


def _leaf_init(path: str, shape, key) -> jax.Array:
    name = path.rsplit("/", 1)[-1]
    f32 = jnp.float32
    if name in ("embed", "lm_head"):
        return jax.random.normal(key, shape, f32) * 0.02
    if name in ("scale", "norm", "conv_b"):
        return jnp.zeros(shape, f32)
    if name == "d_skip":
        return jnp.ones(shape, f32)
    if name == "a_log":  # A = -exp(a_log), A ~ U[1, 16]
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":  # softplus(dt_bias) ~ logU[1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "conv_w":
        return jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    if len(shape) >= 2:
        return jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    raise ValueError(f"no init rule for parameter {path} {shape}")


def path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def leaf_names(shapes: Any) -> List[str]:
    """The parameter leaves' paths, in the order of ``jax.tree.leaves``."""
    return [path_str(path) for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def norms(tree: Any, n_layers: int):
    """Traceable. The norm of each parameter leaf, and the norms of the
    model's leaves, where a leaf stacked over the layers (under ``stack/``,
    leading axis ``n_layers``) counts once for each layer."""
    leaves, layers = [], []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sq = jnp.square(leaf.astype(jnp.float32))
        leaves.append(jnp.sqrt(jnp.sum(sq)))
        if path_str(path).startswith("stack/") and leaf.ndim and leaf.shape[0] == n_layers:
            layers.append(jnp.sqrt(jnp.sum(sq.reshape(n_layers, -1), axis=1)))
        else:
            layers.append(leaves[-1][None])
    return jnp.stack(leaves), jnp.concatenate(layers)


def make_init(shapes: Any) -> Callable[[jax.Array], Any]:
    """``shapes``: the program's parameter tree of ShapeDtypeStructs.
    Returns ``init(key) -> params``, traceable (jit it once)."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            leaves.append(_leaf_init(path_str(path), s.shape, jax.random.fold_in(key, i)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    return init
