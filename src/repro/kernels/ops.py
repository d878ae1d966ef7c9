"""jit'd wrappers around the Pallas kernels with backend dispatch.

On the CPU backend kernels run with ``interpret=True`` — the kernel body
executes in Python for correctness validation; on a TPU backend
``interpret=False`` compiles to Mosaic; any other backend is an error.  The
model layer calls these through config flags (``use_flash_kernel`` /
``use_scan_kernels``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_attention import (
    BWD_BLOCKS,
    FWD_BLOCKS,
    Spec,
    default_block,
    flash_attention_bwd,
    flash_attention_fwd,
)
from .rg_lru import rg_lru_scan_blocked
from .ssd import ssd_chunk_scan_bwd, ssd_chunk_scan_fwd


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(f"Pallas kernels target TPU (interpreted on CPU), not {backend!r}")
    return backend == "cpu"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, spec, bwd_spec):
    return flash_attention_fwd(q, k, v, spec, with_lse=False, interpret=_interpret())[0]


def _flash_fwd(q, k, v, spec, bwd_spec):
    o, lse = flash_attention_fwd(q, k, v, spec, with_lse=True, interpret=_interpret())
    return o, (q, k, v, o, lse)


def _flash_bwd(spec, bwd_spec, res, do):
    return flash_attention_bwd(*res, do, bwd_spec, interpret=_interpret())


# Under remat the primal pass then runs the forward without the
# log-sum-exp, and the backward pass runs the forward that writes it.
_flash.defvjp(_flash_fwd, _flash_bwd, optimize_remat=True)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "scale"))
def flash_attention(
    q: jax.Array,  # (B, S, H, d)
    k: jax.Array,  # (B, T, K, d)
    v: jax.Array,  # (B, T, K, d)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """FlashAttention with GQA; returns (B, S, H, d). ``scale``: the softmax
    scale, 1 / sqrt(d) when None; ``block_q``/``block_k``: every kernel's
    blocks, each kernel's preferred ones that divide the sequences when
    None.  Differentiable: the gradient is a dK/dV and a dQ kernel."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]

    def spec(blocks):
        return Spec(heads=h, kv_heads=kh, head_dim=d,
                    scale=1.0 / math.sqrt(d) if scale is None else scale,
                    causal=causal, window=window,
                    block_q=block_q or default_block(s, blocks[0]),
                    block_k=block_k or default_block(t, blocks[1]))

    out = _flash(q.reshape(b, s, h * d), k.reshape(b, t, kh * d), v.reshape(b, t, kh * d),
                 spec(FWD_BLOCKS), spec(BWD_BLOCKS))
    return out.reshape(b, s, h, d)


@partial(jax.jit, static_argnames=("block_t", "block_n"))
def rg_lru_scan(a: jax.Array, bx: jax.Array, *, block_t: int = 16, block_n: int = 128) -> jax.Array:
    """Blocked linear scan: h_t = a_t h_{t-1} + bx_t.  (B, S, N) fp32."""
    return rg_lru_scan_blocked(a, bx, block_t=block_t, block_n=block_n, interpret=_interpret())


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_scan(x, dt, a, b_in, c_in, chunk):
    y, final, _ = ssd_chunk_scan_fwd(x, dt, a, b_in, c_in, chunk=chunk, save_states=False,
                                     interpret=_interpret())
    return y, final


def _ssd_scan_fwd(x, dt, a, b_in, c_in, chunk):
    y, final, res = ssd_chunk_scan_fwd(x, dt, a, b_in, c_in, chunk=chunk, save_states=True,
                                       interpret=_interpret())
    return (y, final), res


def _ssd_scan_bwd(chunk, res, cotangents):
    dy, dfinal = cotangents
    return ssd_chunk_scan_bwd(res, dy, dfinal, chunk=chunk, interpret=_interpret())


# Under remat the primal pass then runs the forward without the chunk
# states, and the backward pass runs the forward that keeps them.
_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd, optimize_remat=True)


@partial(jax.jit, static_argnames=("chunk",))
def ssd_chunk_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b_in: jax.Array,
    c_in: jax.Array,
    *,
    chunk: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """Fused SSD chunk scan: y (B, S, H, P) and the final state (B, H, P, N)
    from x (B, S, H, P), dt (B, S, H), a (H,), B and C (B, S, G, N), all
    fp32.  Differentiable: the gradient is a second Pallas kernel, a reverse
    sweep over the chunks."""
    return _ssd_scan(x, dt, a, b_in, c_in, chunk)
