"""Pallas TPU kernel for the RG-LRU linear recurrence (Griffin, arXiv:2402.19427).

h_t = a_t * h_{t-1} + b_t  — elementwise over the channel dim.

TPU adaptation: Griffin ships a custom GPU scan; on TPU the natural shape is
a *blocked linear scan*: grid (B, n_channel_blocks, n_time_blocks), the
channel dim rides the 128-lane VPU, and the carry h lives in VMEM scratch
across the sequential time-block dimension.  Within a block the recurrence
runs as an unrolled elementwise loop — linear work, no log-depth blowup like
``associative_scan`` (which XLA would otherwise materialize S·log S wide).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, h_scratch, *, block_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    h = h_scratch[...]  # (1, block_n)
    for t in range(block_t):  # unrolled: block_t is a compile-time constant
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h  # each row goes straight to the output block
    h_scratch[...] = h


def rg_lru_scan_blocked(
    a: jax.Array,  # (B, S, N) fp32
    bx: jax.Array,  # (B, S, N) fp32
    *,
    block_t: int = 16,
    block_n: int = 128,
    interpret: bool,
) -> jax.Array:
    bsz, s, n = a.shape
    block_t = min(block_t, s)
    block_n = min(block_n, n)
    assert s % block_t == 0 and n % block_n == 0, (s, n, block_t, block_n)
    nt, nn = s // block_t, n // block_n

    def index(ib, inn, it):
        return (ib, it, inn)

    kernel = functools.partial(_kernel, block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nn, nt),  # time is minor-most: sequential, scratch carries h
        in_specs=[
            pl.BlockSpec((1, block_t, block_n), index),
            pl.BlockSpec((1, block_t, block_n), index),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_n), index),
        out_shape=jax.ShapeDtypeStruct((bsz, s, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
        interpret=interpret,
    )(a, bx)
