"""Sharded serving: batched prefill + single-token decode.

This is the level above ``models.attention``'s documented contract: batched
decode produces one token for every sequence per call with a shared cache
length; *continuous batching* — admitting and retiring sequences in fixed
cache slots so the decode step never recompiles — lives here as
:class:`SlotAllocator`.

``jit_prefill_step`` / ``jit_serve_step`` are the AOT entries used by the
dry-run and roofline harnesses (abstract inputs, explicit shardings).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist import sharding as shd
from repro.dist.train import with_act_sharding
from repro.models import cache_init, decode_step, lm_init, prefill


# ----------------------------------------------------------------------------
# Step builders (pure functions; jit at the call site or via jit_* below)
# ----------------------------------------------------------------------------

def make_prefill_step(cfg, max_len: int) -> Callable:
    """(params, batch) -> (last_logits, cache); batch keys mirror training
    minus labels (tokens + optional patches/frames)."""

    def prefill_step(params, batch):
        return prefill(
            cfg,
            params,
            batch["tokens"],
            max_len,
            patches=batch.get("patches"),
            frames=batch.get("frames"),
        )

    return prefill_step


def make_decode_step(cfg) -> Callable:
    """(params, cache, token) -> (logits, cache): one token per sequence."""

    def serve_step(params, cache, token):
        return decode_step(cfg, params, cache, token)

    return serve_step


# ----------------------------------------------------------------------------
# Abstract inputs
# ----------------------------------------------------------------------------

def prefill_batch_shapes(cfg, global_batch: int, seq_len: int) -> Dict[str, jax.ShapeDtypeStruct]:
    text_len = seq_len - (cfg.frontend.n_tokens if cfg.frontend else 0)
    shapes = {"tokens": jax.ShapeDtypeStruct((global_batch, text_len), jnp.int32)}
    if cfg.frontend is not None:
        shapes["patches"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.frontend.n_tokens, cfg.frontend.dim), jnp.bfloat16
        )
    if cfg.encoder is not None:
        shapes["frames"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16
        )
    return shapes


def abstract_cache(cfg, batch: int, max_len: int):
    """Decode-cache ShapeDtypeStructs (includes cross-attention K/V for
    enc-dec archs, so decode needs no encoder input)."""

    def build():
        params = lm_init(jax.random.PRNGKey(0), cfg)
        frames = None
        if cfg.encoder is not None:
            frames = jnp.zeros((batch, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16)
        return cache_init(cfg, params, batch, max_len, frames=frames)

    return jax.eval_shape(build)


def _abstract_params(cfg):
    return jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))


def jit_prefill_step(cfg, mesh, global_batch: int, seq_len: int, max_len: Optional[int] = None):
    """Returns (jitted, (params_s, batch_s)) for AOT lowering on ``mesh``."""
    cfg = with_act_sharding(cfg, mesh)
    max_len = max_len or seq_len
    batch_shapes = prefill_batch_shapes(cfg, global_batch, seq_len)
    params_shapes = _abstract_params(cfg)
    params_s = shd.with_shardings(params_shapes, shd.params_shardings(mesh, params_shapes))
    batch_s = shd.with_shardings(batch_shapes, shd.batch_shardings(mesh, batch_shapes))
    jitted = jax.jit(make_prefill_step(cfg, max_len))
    return jitted, (params_s, batch_s)


def jit_serve_step(cfg, mesh, global_batch: int, seq_len: int):
    """Returns (jitted, (params_s, cache_s, tok_s)): one decode step against
    a cache of ``seq_len`` already-cached tokens."""
    cfg = with_act_sharding(cfg, mesh)
    params_shapes = _abstract_params(cfg)
    cache_shapes = abstract_cache(cfg, global_batch, seq_len)
    tok_shapes = jax.ShapeDtypeStruct((global_batch, 1), jnp.int32)
    params_s = shd.with_shardings(params_shapes, shd.params_shardings(mesh, params_shapes))
    cache_s = shd.with_shardings(cache_shapes, shd.cache_shardings(mesh, cache_shapes))
    tok_s = jax.ShapeDtypeStruct(
        tok_shapes.shape, tok_shapes.dtype,
        sharding=jax.sharding.NamedSharding(mesh, shd.batch_spec(mesh, tok_shapes.shape)),
    )
    jitted = jax.jit(make_decode_step(cfg))
    return jitted, (params_s, cache_s, tok_s)


# ----------------------------------------------------------------------------
# Continuous batching (host-side slot bookkeeping; shapes stay static)
# ----------------------------------------------------------------------------

@dataclass
class SlotAllocator:
    """Fixed-size decode slots for continuous batching.

    The jitted decode step has a static batch dimension; sequences are
    admitted into free slots and retired on EOS/length, so arrivals never
    trigger recompilation.  Purely host-side: the device-side cache is the
    caller's pytree, slot occupancy only gates which rows are live.
    """

    n_slots: int
    active: List[Optional[Any]] = field(default_factory=list)
    admitted: int = 0
    retired: int = 0

    def __post_init__(self):
        if not self.active:
            self.active = [None] * self.n_slots

    @property
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.active) if s is None]

    @property
    def live_mask(self) -> List[bool]:
        return [s is not None for s in self.active]

    def admit(self, request: Any) -> int:
        """Place a request in a free slot; raises when saturated."""
        free = self.free_slots
        if not free:
            raise RuntimeError("no free decode slots")
        slot = free[0]
        self.active[slot] = request
        self.admitted += 1
        return slot

    def retire(self, slot: int) -> Any:
        request = self.active[slot]
        if request is None:
            raise KeyError(f"slot {slot} is not live")
        self.active[slot] = None
        self.retired += 1
        return request

    def utilization(self) -> float:
        return sum(self.live_mask) / max(self.n_slots, 1)
