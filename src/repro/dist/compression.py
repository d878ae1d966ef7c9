"""Gradient compression for the data-parallel all-reduce.

Two classic schemes, both pure jax (shard_map-compatible):

  * int8 quantized all-reduce (``int8_psum``): a shared per-tensor scale
    (pmax across the axis) keeps the integer sum exact; the only error is
    the local round-to-nearest, bounded by scale/2 per element.
  * top-k with error feedback (:class:`TopKEF`): only the k largest-
    magnitude entries are sent each step, the residual re-enters the next
    step's gradient (Stich et al., 2018) — mass is conserved exactly:
    ``sent + residual == grad + carried_error``.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def int8_quantize(g: jax.Array, axis_name: str | None = None) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization: returns (q, scale) with
    ``g ~= q * scale``.  Inside a shard_map, pass ``axis_name`` to share the
    scale across the axis (required for an exact integer psum)."""
    amax = jnp.max(jnp.abs(g))
    if axis_name is not None:
        amax = jax.lax.pmax(amax, axis_name)
    scale = (amax / 127.0).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / safe), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def int8_psum(g: jax.Array, axis_name: str) -> jax.Array:
    """Mean of ``g`` across ``axis_name`` over an int8 wire format.

    Quantize with the axis-shared scale, sum the int32-widened payload
    (exact), rescale, divide by the axis size.  Wire bytes: 1/4 of fp32.
    """
    q, scale = int8_quantize(g, axis_name=axis_name)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale / n.astype(jnp.float32)


# ----------------------------------------------------------------------------
# Top-k sparsification with error feedback
# ----------------------------------------------------------------------------

def _topk_leaf(acc: jax.Array, k_fraction: float) -> jax.Array:
    flat = acc.reshape(-1)
    k = max(1, int(flat.size * k_fraction))
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    sparse = jnp.zeros_like(flat).at[idx].set(flat[idx])
    return sparse.reshape(acc.shape)


class TopKEF:
    """Top-k gradient sparsification with per-leaf error feedback.

    Usage::

        err = TopKEF.init(grads)               # zero residuals, once
        sent, err = TopKEF.compress(grads, err, k_fraction=0.01)
        # all-reduce `sent` (sparse), apply; `err` carries to next step
    """

    @staticmethod
    def init(grads: Any) -> Any:
        return jax.tree.map(jnp.zeros_like, grads)

    @staticmethod
    def compress(grads: Any, error: Any, k_fraction: float = 0.01) -> Tuple[Any, Any]:
        """Returns (sparse, new_error) with sparse + new_error == grads + error
        exactly (elementwise: each entry lands in exactly one of the two)."""
        acc = jax.tree.map(jnp.add, grads, error)
        sparse = jax.tree.map(lambda a: _topk_leaf(a, k_fraction), acc)
        new_error = jax.tree.map(jnp.subtract, acc, sparse)
        return sparse, new_error
