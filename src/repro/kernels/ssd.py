"""Pallas TPU kernel for the Mamba-2 SSD chunk scan (arXiv:2405.21060).

One (batch, head) slice per grid row; chunks iterate on the sequential
minor-most grid dim with the SSM state (P, N) carried in VMEM scratch.
Per chunk, everything is dense MXU work — exactly the paper's state-space
duality: intra-chunk attention-like matmuls + low-rank inter-chunk state
passing:

    scores  = (C B^T) ⊙ decay        (L, L) lower-tri
    y_diag  = scores @ (x·dt)        (L, P)
    y_off   = (C ⊙ decay_in) @ h     (L, P)
    h'      = chunk_decay · h + (B ⊙ decay_out)^T @ (x·dt)

The GPU implementation leans on warp shuffles for the cumsum; Mosaic has
no cumsum, so here the cumulative sums are matmuls with the (L, L)
lower-triangular mask the kernel builds anyway, and the matmuls dominate —
the kernel keeps all of them in one VMEM-resident fusion per chunk.  The
per-head decay rates sit whole in SMEM and are read by grid row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_scratch, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    x = x_ref[0].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0].astype(jnp.float32)  # (L, 1)
    a = a_ref[pl.program_id(0)]  # this head's decay rate, from SMEM
    b = b_ref[0].astype(jnp.float32)  # (L, N)
    c = c_ref[0].astype(jnp.float32)  # (L, N)
    h = h_scratch[...]  # (P, N) fp32

    log_a = dt * a  # (L, 1) negative

    # Every matmul runs at HIGHEST: at the MXU's default precision the f32
    # operands are rounded to bf16 and the scan loses about two digits.
    # Inclusive cumsum of log_a as a matmul with the lower-triangular mask,
    # once as a column (acs_i) and once as a row (acs_j).
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = li >= lj
    trif = tri.astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST
    acs = jax.lax.dot_general(
        trif, log_a, (((1,), (0,)), ((), ())), precision=exact,
        preferred_element_type=jnp.float32)  # (L, 1)
    acs_row = jax.lax.dot_general(
        log_a, trif, (((0,), (1,)), ((), ())), precision=exact,
        preferred_element_type=jnp.float32)  # (1, L)

    # intra-chunk: scores_ij = exp(acs_i - acs_j) for j <= i
    decay = jnp.where(tri, jnp.exp(acs - acs_row), 0.0)  # (L, L)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())), precision=exact,
                             preferred_element_type=jnp.float32)
    scores = cb * decay
    xdt = x * dt  # (L, P)
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())), precision=exact,
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the incoming state
    decay_in = jnp.exp(acs)  # (L, 1)
    y = y + jax.lax.dot_general(
        c * decay_in, h, (((1,), (1,)), ((), ())), precision=exact,
        preferred_element_type=jnp.float32)

    # state update: h' = exp(sum log_a) * h + (b * decay_out)^T @ xdt
    total = jnp.sum(log_a)
    decay_out = jnp.exp(total - acs)  # (L, 1)
    h_new = jnp.exp(total) * h + jax.lax.dot_general(
        xdt, b * decay_out, (((0,), (0,)), ((), ())), precision=exact,
        preferred_element_type=jnp.float32)  # (P, N)

    y_ref[0] = y.astype(y_ref.dtype)
    h_scratch[...] = h_new


def ssd_chunk_scan_blocked(
    x: jax.Array,  # (B, S, H, P) fp32
    dt: jax.Array,  # (B, S, H) fp32 post-softplus
    a: jax.Array,  # (H,) fp32 negative
    b_in: jax.Array,  # (B, S, G, N) fp32 (G must divide H; broadcast outside)
    c_in: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 64,
    interpret: bool,
):
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    assert s % chunk == 0
    nc = s // chunk
    rep = h // g

    # (B, H, S, ...) layouts; one (batch, head) pair per grid row.
    xt = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    dtt = dt.transpose(0, 2, 1).reshape(bsz * h, s, 1)
    bt = jnp.repeat(b_in, rep, axis=2).transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
    ct = jnp.repeat(c_in, rep, axis=2).transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
    ah = jnp.tile(a, bsz)  # (B*H,): one decay rate per grid row

    kernel = functools.partial(_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(bsz * h, 1, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda ib, _, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, _, ic: (ib, ic, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole (B*H,) array
            pl.BlockSpec((1, chunk, n), lambda ib, _, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, _, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda ib, _, ic: (ib, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, ah, bt, ct)
    return y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
