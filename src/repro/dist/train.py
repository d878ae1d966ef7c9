"""Sharded training step.

``make_train_step`` builds the pure (params, opt_state, batch) -> (params,
opt_state, stats) function; data/FSDP/TP placement is carried entirely by
input shardings + the activation constraints installed by
``with_act_sharding``, so the same step runs unchanged on one device or a
pod mesh (the numerical-equivalence test in tests/test_dist_features.py
holds it to that).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.jax_events import scope
from repro.dist import sharding as shd
from repro.models import lm_init, lm_loss
from repro.optim import adamw


def with_act_sharding(cfg, mesh):
    """Config with residual-stream activation constraints for ``mesh``.

    No-op (returns ``cfg`` unchanged) when the mesh has no batch/model axes,
    so CPU smoke paths keep act_pspec=None."""
    axes = shd.act_axes(mesh)
    return cfg.scaled(act_pspec=axes) if axes is not None else cfg


def _cast_params_for_compute(params, dtype):
    """Mixed precision: >=2D fp32 weights compute in bf16; fp32 masters stay
    in the optimizer (halves FSDP all-gather wire bytes)."""
    target = jnp.dtype(dtype)

    def cast(p):
        if p.ndim >= 2 and p.dtype == jnp.float32:
            return p.astype(target)
        return p

    with scope("param_cast"):
        return jax.tree.map(cast, params)


def make_train_step(cfg, opt_cfg: adamw.AdamWConfig) -> Callable:
    """One optimizer step: loss + grad + AdamW update.

    stats: loss, ce, aux (MoE balance), grad_norm, lr.
    """

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            if cfg.params_compute_dtype == "bfloat16":
                p = _cast_params_for_compute(p, jnp.bfloat16)
            return lm_loss(cfg, p, batch)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_params, new_opt, opt_stats = adamw.update(opt_cfg, grads, opt_state, params)
        stats = {"loss": loss, "ce": metrics["ce"], "aux": metrics["aux"], **opt_stats}
        return new_params, new_opt, stats

    return train_step


# ----------------------------------------------------------------------------
# Abstract inputs (ShapeDtypeStructs only, no allocation)
# ----------------------------------------------------------------------------

def batch_shapes(cfg, global_batch: int, seq_len: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract train batch matching the data pipeline's layout: ``seq_len``
    is the *total* sequence budget; VLM patch tokens come out of it."""
    text_len = seq_len - (cfg.frontend.n_tokens if cfg.frontend else 0)
    shapes = {
        "tokens": jax.ShapeDtypeStruct((global_batch, text_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((global_batch, text_len), jnp.int32),
    }
    if cfg.frontend is not None:
        shapes["patches"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.frontend.n_tokens, cfg.frontend.dim), jnp.bfloat16
        )
    if cfg.encoder is not None:
        shapes["frames"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16
        )
    return shapes


def abstract_state(cfg) -> Tuple[Any, Any]:
    """(params, opt_state) as ShapeDtypeStruct trees."""
    params = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(adamw.init, params)
    return params, opt

