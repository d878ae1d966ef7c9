"""Pieces every plain reference shares: matmuls in a stated precision,
RMS norm, rotary embedding, cross entropy, and the loop that runs three
AdamW steps in blocks of rows.

``mode`` is the precision of every matmul:
  "f32"  float32 operands at Precision.HIGHEST (the reference);
  "fp8"  operands rounded to float8_e4m3fn with one scale per tensor, then
         multiplied as above, and in the backward pass the incoming
         gradient rounded to float8_e5m2 the same way (the control: the
         step below the bfloat16 matmuls the configuration states).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
F8_GRAD = jnp.float8_e5m2
F8_GRAD_MAX = 57344.0


def _quantize(a: jax.Array, dtype, top: float) -> jax.Array:
    """Round to an 8-bit float with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(eq, _quantize(a, F8, F8_MAX), _quantize(b, F8, F8_MAX), precision=HIGHEST)


def _mm_fp8_fwd(eq, a, b):
    qa, qb = _quantize(a, F8, F8_MAX), _quantize(b, F8, F8_MAX)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)


def _mm_fp8_bwd(eq, saved, g):
    # the usual fp8 recipe: gradients in e5m2, each tensor with its own scale
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST), *saved)
    return vjp(_quantize(g, F8_GRAD, F8_GRAD_MAX))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(eq: str, a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        return _mm_fp8(eq, a, b)
    if mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """The program stores a norm's weight as ``1 + scale``."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def rotate_half_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (S, heads, d): rotary embedding in the rotate-half layout."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def cross_entropy_sum(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Sum over positions of -log softmax(logits)[label]; logits (S, V)."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[:, 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


# ---------------------------------------------------------------------------
# Three AdamW steps, the gradient taken one row at a time
# ---------------------------------------------------------------------------

def train_reference(
    row_loss: Callable,  # (params, tokens (S,), labels (S,)) -> sum of CE over the row
    init: Callable,  # key -> params (the seeded weights both sides start from)
    key: jax.Array,
    batches: Sequence[Dict[str, np.ndarray]],
    opt: Dict[str, float],
    norms: Callable,  # tree -> (norm per leaf, norm per layer leaf), traceable
) -> Dict[str, Any]:
    """Losses of each step, the clipped first gradient's norms, and the
    norms of the change in parameters after all the steps, each per leaf
    and per layer leaf as ``norms`` gives them.

    The optimizer is AdamW with global-norm clipping, as the configuration
    states it; its moments are kept on the host between steps so that the
    parameters, the gradient being summed and one row's activations are
    all the device holds."""
    with jax.default_matmul_precision("highest"):
        params = jax.jit(init)(key)
        n_tok = batches[0]["tokens"].size

        @partial(jax.jit, donate_argnums=(1,))
        def add_row_grad(params, acc, tokens, labels):
            loss, g = jax.value_and_grad(row_loss)(params, tokens, labels)
            return loss, jax.tree.map(lambda a, b: a + b / n_tok, acc, g)

        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        gnorm_fn = jax.jit(lambda g: jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(g))))

        b1, b2, eps, lr, wd, clip = (opt[k] for k in ("b1", "b2", "eps", "lr", "weight_decay", "grad_clip_norm"))

        @jax.jit
        def leaf_update(p, g, m, v, scale, t):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v

        leaves0 = jax.tree.leaves(params)
        m_host = [np.zeros(l.shape, np.float32) for l in leaves0]
        v_host = [np.zeros(l.shape, np.float32) for l in leaves0]
        norms_fn = jax.jit(norms)
        losses, g1 = [], None
        for t, batch in enumerate(batches, start=1):
            acc = zeros(params)
            total = 0.0
            for r in range(batch["tokens"].shape[0]):
                loss, acc = add_row_grad(params, acc, jnp.asarray(batch["tokens"][r]),
                                         jnp.asarray(batch["labels"][r]))
                total += float(loss)
            losses.append(total / n_tok)
            gnorm = float(gnorm_fn(acc))
            scale = min(1.0, clip / (gnorm + 1e-9))
            if t == 1:
                g1 = [np.asarray(n, np.float64) * scale for n in jax.device_get(norms_fn(acc))]
            flat_p, tree = jax.tree.flatten(params)
            flat_g = jax.tree.leaves(acc)
            del acc
            new_p = []
            for i, (p, g) in enumerate(zip(flat_p, flat_g)):
                np_, m, v = leaf_update(p, g, jnp.asarray(m_host[i]), jnp.asarray(v_host[i]),
                                        jnp.float32(scale), jnp.float32(t))
                m_host[i], v_host[i] = np.asarray(m), np.asarray(v)
                new_p.append(np_)
            del flat_p, flat_g
            params = jax.tree.unflatten(tree, new_p)
            del new_p
        delta = jax.device_get(jax.jit(lambda p, k: norms(jax.tree.map(jnp.subtract, p, init(k))))(params, key))
        return {"losses": losses,
                "grad_norms": g1[0].tolist(), "grad_layer_norms": g1[1].tolist(),
                "delta_norms": np.asarray(delta[0]).tolist(), "delta_layer_norms": np.asarray(delta[1]).tolist()}
