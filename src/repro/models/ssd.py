"""Mamba-2 block — State Space Duality / SSD (arXiv:2405.21060).

Training/prefill use the chunked SSD algorithm (intra-chunk "attention-like"
einsums + inter-chunk linear recurrence over per-chunk states), which maps
onto the MXU as dense matmuls — exactly the duality the paper exploits; the
Pallas kernel in ``repro.kernels.ssd`` implements the fused chunk-scan for
the TPU target.  Decode keeps the O(1) recurrent state h (B, H, P, N).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.jax_events import scope

from .layers import dense_init, rms_norm

Params = Dict[str, Any]


def ssd_init(
    key,
    d_model: int,
    d_inner: int,
    head_dim: int,
    d_state: int,
    n_groups: int = 1,
    conv_width: int = 4,
) -> Params:
    n_heads = d_inner // head_dim
    ks = jax.random.split(key, 4)
    d_conv_in = d_inner + 2 * n_groups * d_state  # x, B, C share the conv
    d_in_proj = 2 * d_inner + 2 * n_groups * d_state + n_heads  # +z, +dt
    # dt bias init so softplus(dt_bias) ~ U[1e-3, 1e-1] (mamba2 default)
    u = jax.random.uniform(ks[0], (n_heads,), minval=math.log(1e-3), maxval=math.log(1e-1))
    dt_bias = jnp.log(jnp.expm1(jnp.exp(u)))
    return {
        "in_proj": dense_init(ks[1], d_model, d_in_proj),
        "conv_w": jax.random.normal(ks[2], (conv_width, d_conv_in), jnp.float32)
        * (1.0 / math.sqrt(conv_width)),
        "conv_b": jnp.zeros((d_conv_in,), jnp.float32),
        "a_log": jnp.log(jnp.arange(1, n_heads + 1, dtype=jnp.float32)),  # A = -exp(a_log)
        "dt_bias": dt_bias.astype(jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm": jnp.zeros((d_inner,), jnp.float32),
        "out_proj": dense_init(ks[3], d_inner, d_model),
    }


def _split_proj(params: Params, x: jax.Array, d_inner: int, n_groups: int, d_state: int, n_heads: int):
    dtype = x.dtype
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(dtype))
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * n_groups * d_state], axis=-1)
    return z, xbc, dt


def _conv(xbc: jax.Array, w: jax.Array, b: jax.Array, state: Optional[jax.Array] = None):
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)
    out = jnp.zeros_like(xbc)
    for i in range(width):
        out = out + xp[:, i : i + xbc.shape[1]] * w[i].astype(xbc.dtype)
    out = jax.nn.silu(out + b.astype(xbc.dtype))
    new_state = xp[:, -(width - 1) :]
    return out, new_state


def segsum(log_a: jax.Array) -> jax.Array:
    """Stable 'segment sum': out[..., i, j] = sum_{k=j+1..i} log_a[..., k],
    lower-triangular, -inf above the diagonal.  log_a (..., L)."""
    l = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # i row, j col: sum_{j+1..i}
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked_ref(
    x: jax.Array,  # (B, S, H, P) fp32
    dt: jax.Array,  # (B, S, H) fp32 (post-softplus)
    a: jax.Array,  # (H,) fp32 negative
    b_in: jax.Array,  # (B, S, G, N) fp32
    c_in: jax.Array,  # (B, S, G, N) fp32
    chunk: int = 64,
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD; returns (y (B,S,H,P), final state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g

    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = jnp.repeat(b_in.reshape(bsz, nc, chunk, g, n), rep, axis=3)  # (B,nc,L,H,N)
    cc = jnp.repeat(c_in.reshape(bsz, nc, chunk, g, n), rep, axis=3)

    log_a = dtc * a  # (B,nc,L,H) negative increments
    log_a_h = log_a.transpose(0, 1, 3, 2)  # (B,nc,H,L)
    acs = jnp.cumsum(log_a_h, axis=-1)  # within-chunk cumulative

    # intra-chunk (diagonal block): Y_ij = C_i . B_j * exp(acs_i - acs_j) * dt_j x_j
    l_mat = jnp.exp(segsum(log_a_h))  # (B,nc,H,L,L)
    xdt = xc * dtc[..., None]  # (B,nc,L,H,P)
    y_diag = jnp.einsum("bclhn,bcshn,bchls,bcshp->bclhp", cc, bc, l_mat, xdt)

    # per-chunk input states: sum_j exp(acs_L - acs_j) dt_j B_j x_j
    decay_states = jnp.exp(acs[..., -1:] - acs)  # (B,nc,H,L)
    states = jnp.einsum("bclhn,bchl,bclhp->bchpn", bc, decay_states, xdt)

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.sum(log_a_h, axis=-1))  # (B,nc,H)

    def body(carry, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        new = carry * dec[..., None, None] + st
        return new, carry  # emit PREVIOUS state (state entering this chunk)

    init = h0 if h0 is not None else jnp.zeros((bsz, h, p, n), x.dtype)
    final, prev_states = jax.lax.scan(
        body,
        init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)

    # inter-chunk contribution: C_i . (decay_in_i * prev_state)
    decay_in = jnp.exp(acs)  # (B,nc,H,L)
    y_off = jnp.einsum("bclhn,bchl,bchpn->bclhp", cc, decay_in, prev_states)

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y, final


def ssd_apply(
    params: Params,
    x: jax.Array,
    *,
    d_inner: int,
    head_dim: int,
    d_state: int,
    n_groups: int = 1,
    chunk: int = 64,
    use_kernel: bool = False,
) -> jax.Array:
    """Full-sequence Mamba-2 block. x (B,S,D)."""
    dtype = x.dtype
    n_heads = d_inner // head_dim
    with scope("ssd_proj"):
        z, xbc, dt = _split_proj(params, x, d_inner, n_groups, d_state, n_heads)
        xbc, _ = _conv(xbc, params["conv_w"], params["conv_b"])
        xin, b_in, c_in = jnp.split(xbc, [d_inner, d_inner + n_groups * d_state], axis=-1)

        bsz, s, _ = x.shape
        xh = xin.astype(jnp.float32).reshape(bsz, s, n_heads, head_dim)
        bi = b_in.astype(jnp.float32).reshape(bsz, s, n_groups, d_state)
        ci = c_in.astype(jnp.float32).reshape(bsz, s, n_groups, d_state)
        dtv = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,H)
        a = -jnp.exp(params["a_log"])  # (H,)

        with scope("ssd_scan"):
            if use_kernel:
                from repro.kernels import ops as _kops

                y, _ = _kops.ssd_chunk_scan(xh, dtv, a, bi, ci, chunk=chunk)
            else:
                y, _ = ssd_chunked_ref(xh, dtv, a, bi, ci, chunk=chunk)
        y = y + xh * params["d_skip"][None, None, :, None]
        y = y.reshape(bsz, s, d_inner).astype(dtype)
        y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(dtype), params["norm"])
        return jnp.einsum("bsn,nd->bsd", y, params["out_proj"].astype(dtype))


# -- decode -------------------------------------------------------------------

def ssd_state_init(batch: int, d_inner: int, head_dim: int, d_state: int, n_groups: int = 1, conv_width: int = 4) -> Params:
    n_heads = d_inner // head_dim
    return {
        "h": jnp.zeros((batch, n_heads, head_dim, d_state), jnp.float32),
        "conv": jnp.zeros((batch, conv_width - 1, d_inner + 2 * n_groups * d_state), jnp.bfloat16),
    }


def ssd_prefill_state(
    params: Params,
    x: jax.Array,
    *,
    d_inner: int,
    head_dim: int,
    d_state: int,
    n_groups: int = 1,
    chunk: int = 64,
) -> Params:
    dtype = x.dtype
    n_heads = d_inner // head_dim
    z, xbc, dt = _split_proj(params, x, d_inner, n_groups, d_state, n_heads)
    xbc_conv, conv_state = _conv(xbc, params["conv_w"], params["conv_b"])
    xin, b_in, c_in = jnp.split(xbc_conv, [d_inner, d_inner + n_groups * d_state], axis=-1)
    bsz, s, _ = x.shape
    xh = xin.astype(jnp.float32).reshape(bsz, s, n_heads, head_dim)
    bi = b_in.astype(jnp.float32).reshape(bsz, s, n_groups, d_state)
    ci = c_in.astype(jnp.float32).reshape(bsz, s, n_groups, d_state)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    _, h = ssd_chunked_ref(xh, dtv, a, bi, ci, chunk=chunk)
    return {"h": h, "conv": conv_state.astype(jnp.bfloat16)}


def ssd_decode(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    state: Params,
    *,
    d_inner: int,
    head_dim: int,
    d_state: int,
    n_groups: int = 1,
) -> Tuple[jax.Array, Params]:
    dtype = x.dtype
    n_heads = d_inner // head_dim
    z, xbc, dt = _split_proj(params, x, d_inner, n_groups, d_state, n_heads)
    xbc, conv_state = _conv(xbc, params["conv_w"], params["conv_b"], state["conv"])
    xin, b_in, c_in = jnp.split(xbc, [d_inner, d_inner + n_groups * d_state], axis=-1)
    bsz = x.shape[0]
    xh = xin.astype(jnp.float32).reshape(bsz, n_heads, head_dim)
    bi = b_in.astype(jnp.float32).reshape(bsz, n_groups, d_state)
    ci = c_in.astype(jnp.float32).reshape(bsz, n_groups, d_state)
    rep = n_heads // n_groups
    bi = jnp.repeat(bi, rep, axis=1)  # (B,H,N)
    ci = jnp.repeat(ci, rep, axis=1)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)[:, 0] + params["dt_bias"])  # (B,H)
    a = -jnp.exp(params["a_log"])
    decay = jnp.exp(dtv * a)  # (B,H)
    h = state["h"] * decay[..., None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dtv, xh, bi
    )
    y = jnp.einsum("bhpn,bhn->bhp", h, ci) + xh * params["d_skip"][None, :, None]
    y = y.reshape(bsz, 1, d_inner).astype(dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(dtype), params["norm"])
    out = jnp.einsum("bsn,nd->bsd", y, params["out_proj"].astype(dtype))
    return out, {"h": h, "conv": conv_state.astype(jnp.bfloat16)}
