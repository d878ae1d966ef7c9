"""Pallas TPU kernels for the Mamba-2 SSD chunk scan (arXiv:2405.21060):
a forward sweep over chunks and a reverse sweep for its gradient.

A grid row holds one batch element and a block of heads that share one
B/C group; chunks iterate on the sequential minor-most grid dim.  x, y and
their cotangents stay in the model's layout, (B, S, H P) with the heads
side by side in lanes, so no transpose runs around the kernels.  Inside,
heads are taken a lane group at a time: 128 // P heads whose P-wide lane
slices make one 128-lane tile, with their SSM states (P, N) stacked into
one (128, N) VMEM block.  Per chunk, everything is dense MXU work -- the
paper's state-space duality: intra-chunk attention-like matmuls plus
low-rank inter-chunk state passing:

    scores  = (C B^T) * decay        (L, L) lower-tri, per head
    y_diag  = scores @ (x dt)        (L, P)
    y_off   = decay_in * (C @ h^T)   (L, P), a lane group at once
    h'      = chunk_decay h + (x dt * decay_out)^T @ B   (P, N), likewise

``C B^T`` depends on the group only, so it is computed once per chunk for
all heads of the block.  The GPU implementation leans on warp shuffles
for the cumsum; Mosaic has no cumsum, so here the cumulative sums are
masked sums over the (L, L) lower triangle the kernel builds anyway.

Under differentiation the forward kernel also writes the states entering
each chunk, and the backward kernel reads them back while it carries dh
from the last chunk to the first; it sums dB and dC over the heads of its
block, so only (heads / block) partials per group reach HBM.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Every matmul runs at HIGHEST: at the MXU's default precision the f32
# operands are rounded to bf16 and the scan loses about two digits.
_EXACT = jax.lax.Precision.HIGHEST
# Heads handled by one grid row; more heads per row means fewer grid steps
# and fewer dB/dC partials, at (heads x P x N) f32 of carried state each.
_MAX_HEADS_PER_BLOCK = 32
# The MXU is 128 lanes wide: heads of P < 128 lanes are taken a lane group
# of 128 // P at a time, so the state and inter-chunk matmuls fill it.
_LANES = 128


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_EXACT,
                               preferred_element_type=jnp.float32)


def _mm(a, b):  # a @ b
    return _dot(a, b, ((1,), (0,)))


def _mm_nt(a, b):  # a @ b.T
    return _dot(a, b, ((1,), (1,)))


def _mm_tn(a, b):  # a.T @ b
    return _dot(a, b, ((0,), (0,)))


def _heads_per_block(heads_per_group: int) -> int:
    """The largest divisor of a group's head count up to the block limit."""
    return max(d for d in range(1, min(heads_per_group, _MAX_HEADS_PER_BLOCK) + 1)
               if heads_per_group % d == 0)


def _heads_per_lane_group(hb: int, p: int) -> int:
    """Heads of P lanes each that fill one lane tile and divide the block."""
    return math.gcd(hb, _LANES // p) if _LANES % p == 0 else 1


def _exp(x):
    """exp(x) for x <= 0 (clipped to [-87, 0]) to about 1 ulp.

    Mosaic's exp on a v5e is off by up to 65 ulp, which put the gradient of
    the decay rates, a sum over every position, 10x outside 1e-3 of the
    float64 reference; this takes exp(r) for |r| <= ln(2)/2 from a degree-7
    polynomial and scales it by 2^n through the exponent bits."""
    x = jnp.clip(x, -87.0, 0.0)
    n = jnp.floor(x * 1.4426950408889634 + 0.5)  # x / ln 2, rounded
    r = (x - n * 0.693145751953125) - n * 1.428606765330187e-06  # ln 2 in two parts
    p = 1.0 / 5040
    for c in (1.0 / 720, 1.0 / 120, 1.0 / 24, 1.0 / 6, 0.5, 1.0, 1.0):
        p = p * r + c
    return p * jax.lax.bitcast_convert_type((n.astype(jnp.int32) + 127) << 23, jnp.float32)


def _masks(chunk: int):
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return li >= lj, li == lj


# Vectors within a chunk flip between a (1, L) row and an (L, 1) column,
# and sum along the chunk, through masked sums over the (L, L) square.
# These are exact, and cost the VPU less than a 6-pass MXU product would.
def _col(row, eye):
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _head_terms(dt_row, a, tri, eye):
    """This head's decays within the chunk, from its dt as a (1, L) row.

    Returns the dt column, the inclusive cumsum of log a as a column, the
    masked decay matrix exp(acs_i - acs_j), and the chunk's sum of log a
    as (1, 1)."""
    log_a = dt_row * a  # (1, L) negative
    acs_col = jnp.sum(jnp.where(tri, log_a, 0.0), axis=1, keepdims=True)  # sum_{k <= i}
    decay = jnp.where(tri, _exp(acs_col - _row(acs_col, eye)), 0.0)  # (L, L)
    total = jnp.sum(log_a, axis=1, keepdims=True)  # (1, 1)
    return _col(dt_row, eye), acs_col, decay, total


class _Head(NamedTuple):
    lanes: jax.Array  # (L, kP) bool: this head's lanes of the group
    rows: jax.Array  # (kP, 1) bool: this head's rows of the stacked state
    a: jax.Array  # its decay rate
    decay_in: jax.Array  # (L, 1) exp(acs)
    decay_out: jax.Array  # (L, 1) exp(total - acs)
    chunk_decay: jax.Array  # (1, 1) exp(total)
    decay: jax.Array  # (L, L) masked exp(acs_i - acs_j)


def _group_terms(dt_ref, a_ref, head0, q, *, chunk, k, p, tri, eye):
    """The decays of lane group ``q``'s k heads: a ``_Head`` each, and dt,
    decay_in and decay_out spread over the group's (L, kP) lanes and the
    chunk decay over its (kP, 1) state rows."""
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (chunk, k * p), 1) // p
    row_head = jax.lax.broadcasted_iota(jnp.int32, (k * p, 1), 0) // p
    heads = []
    dt_l = in_l = out_l = jnp.zeros((chunk, k * p), jnp.float32)
    chunk_decay_r = jnp.zeros((k * p, 1), jnp.float32)
    for j in range(k):
        r = q * k + j
        a = a_ref[head0 + r]
        dt_col, acs_col, decay, total = _head_terms(dt_ref[0, 0, 0, pl.ds(r, 1), :], a, tri, eye)
        hd = _Head(lane_head == j, row_head == j, a, _exp(acs_col), _exp(total - acs_col),
                   _exp(total), decay)
        dt_l = jnp.where(hd.lanes, dt_col, dt_l)
        in_l = jnp.where(hd.lanes, hd.decay_in, in_l)
        out_l = jnp.where(hd.lanes, hd.decay_out, out_l)
        chunk_decay_r = jnp.where(hd.rows, hd.chunk_decay, chunk_decay_r)
        heads.append(hd)
    return heads, dt_l, in_l, out_l, chunk_decay_r


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, *refs, chunk, hb, k, p, save_states):
    if save_states:
        y_ref, final_ref, states_ref, h_scratch = refs
    else:
        (y_ref, final_ref, h_scratch), states_ref = refs, None
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    tri, eye = _masks(chunk)
    b = b_ref[0, 0]  # (L, N)
    c = c_ref[0, 0]  # (L, N)
    cb = _mm_nt(c, b)  # (L, L), shared by the block's heads
    head0 = pl.program_id(1) * hb
    w = k * p

    def group(q, carry):
        heads, dt_l, in_l, out_l, chunk_decay_r = _group_terms(
            dt_ref, a_ref, head0, q, chunk=chunk, k=k, p=p, tri=tri, eye=eye)
        off = pl.multiple_of(q * w, w)
        xdt = x_ref[0, :, pl.ds(off, w)] * dt_l  # (L, kP)
        h = h_scratch[q]  # (kP, N): the k heads' states entering this chunk
        # y_diag = scores @ xdt, the k heads' scores stacked on one product
        diag = _mm(jnp.concatenate([cb * hd.decay for hd in heads], axis=0), xdt)  # (kL, kP)
        y = in_l * _mm_nt(c, h)
        for j, hd in enumerate(heads):
            y = jnp.where(hd.lanes, y + diag[j * chunk:(j + 1) * chunk], y)
        y_ref[0, :, pl.ds(off, w)] = y.astype(y_ref.dtype)
        if save_states:
            states_ref[0, q, 0] = h
        h_scratch[q] = chunk_decay_r * h + _mm_tn(xdt * out_l, b)
        return carry

    jax.lax.fori_loop(0, hb // k, group, 0)

    @pl.when(ic == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = h_scratch[...]


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, states_ref, dy_ref, dfinal_ref,
                dx_ref, ddt_ref, dla_ref, db_ref, dc_ref, dh_scratch, *, chunk, hb, k, p):
    @pl.when(pl.program_id(2) == 0)  # the last chunk comes first
    def _init():
        dh_scratch[...] = dfinal_ref[0]

    tri, eye = _masks(chunk)
    b = b_ref[0, 0]  # (L, N)
    c = c_ref[0, 0]
    cb = _mm_nt(c, b)
    head0 = pl.program_id(1) * hb
    w = k * p

    def lane_sum(v, mine):  # (L, kP) -> (L, 1) over one head's lanes
        return jnp.sum(jnp.where(mine, v, 0.0), axis=1, keepdims=True)

    def group(q, carry):
        db, dc, d_cb = carry
        heads, dt_l, in_l, out_l, chunk_decay_r = _group_terms(
            dt_ref, a_ref, head0, q, chunk=chunk, k=k, p=p, tri=tri, eye=eye)
        off = pl.multiple_of(q * w, w)
        x = x_ref[0, :, pl.ds(off, w)]  # (L, kP)
        dy = dy_ref[0, :, pl.ds(off, w)]
        xdt = x * dt_l
        h = states_ref[0, q, 0]  # (kP, N): the states entering this chunk
        g = dh_scratch[q]  # (kP, N): d loss / d the states leaving it
        y_off = _mm_nt(c, h)  # (L, kP): C h^T per head, before decay_in
        bg = _mm_nt(b, g)  # (L, kP)
        dxdt = out_l * bg  # from h' = chunk_decay h + xdt^T @ (B * decay_out)
        dy_off = dy * y_off
        xbg = xdt * bg
        gh = jnp.sum(g * h, axis=1, keepdims=True)  # (kP, 1)
        # y_diag = scores @ xdt; scores = cb * decay
        d_scores_k = _mm_nt(jnp.concatenate([jnp.where(hd.lanes, dy, 0.0) for hd in heads], axis=0),
                            xdt)  # (kL, L): the k heads' d scores stacked
        for j, (mine, rows, a, decay_in, decay_out, chunk_decay, decay) in enumerate(heads):
            scores = cb * decay
            d_scores = d_scores_k[j * chunk:(j + 1) * chunk]
            dxdt = jnp.where(mine, dxdt + _mm_tn(scores, dy), dxdt)
            d_cb = d_cb + d_scores * decay  # dB, dC from it after the loop, once for all heads
            m = d_scores * scores  # d acs_i gets its row sum, d acs_j loses its column sum
            dacs = jnp.sum(m, axis=1, keepdims=True) - _col(jnp.sum(m, axis=0, keepdims=True), eye)
            dacs = dacs + decay_in * lane_sum(dy_off, mine)  # y_off = decay_in * (C @ h^T)
            d_out = decay_out * lane_sum(xbg, mine)  # (L, 1)
            d_total = chunk_decay * jnp.sum(jnp.where(rows, gh, 0.0), axis=0, keepdims=True) + (
                jnp.sum(d_out, axis=0, keepdims=True))
            dacs = dacs - d_out
            # acs = cumsum(log_a) and total = sum(log_a); log_a = dt * a; xdt = x * dt
            dla = jnp.sum(jnp.where(tri, dacs, 0.0), axis=0, keepdims=True) + d_total  # (1, L)
            ddt = _row(lane_sum(dxdt * x, mine), eye) + a * dla
            r = q * k + j
            ddt_ref[0, 0, 0, pl.ds(r, 1), :] = ddt
            dla_ref[0, 0, 0, pl.ds(r, 1), :] = dla
        dy_in = dy * in_l
        dc = dc + _mm(dy_in, h)
        db = db + _mm(xdt * out_l, g)
        dx_ref[0, :, pl.ds(off, w)] = (dxdt * dt_l).astype(dx_ref.dtype)
        dh_scratch[q] = _mm_tn(dy_in, c) + chunk_decay_r * g
        return db, dc, d_cb

    zeros = jnp.zeros(b.shape, jnp.float32)
    db, dc, d_cb = jax.lax.fori_loop(0, hb // k, group, (zeros, zeros, jnp.zeros_like(cb)))
    db_ref[0, 0] = db + _mm_tn(d_cb, c)
    dc_ref[0, 0] = dc + _mm(d_cb, b)


def _dims(xl, bt, h, chunk):
    """Sizes from the kernel layouts of x (B, S, H P) and B (B, G, S, N)."""
    bsz, s, hp = xl.shape
    g, n = bt.shape[1], bt.shape[3]
    hb = _heads_per_block(h // g)
    p = hp // h
    k = _heads_per_lane_group(hb, p)
    return dict(bsz=bsz, s=s, h=h, p=p, g=g, n=n, nc=s // chunk, hb=hb, k=k)


def _specs(dims, chunk, *, reverse: bool):
    """Block specs of the kernels' operands, by layout; ``reverse`` walks
    the chunks from last to first."""
    nc, hb, k, p, n = (dims[key] for key in ("nc", "hb", "k", "p", "n"))
    heads_per_group = dims["h"] // dims["g"]

    def cix(ic):
        return nc - 1 - ic if reverse else ic

    return dict(
        seq=pl.BlockSpec((1, chunk, hb * p), lambda ib, j, ic: (ib, cix(ic), j)),
        row=pl.BlockSpec((1, 1, 1, hb, chunk), lambda ib, j, ic: (ib, j, cix(ic), 0, 0)),
        group=pl.BlockSpec((1, 1, chunk, n),
                           lambda ib, j, ic: (ib, j * hb // heads_per_group, cix(ic), 0)),
        part=pl.BlockSpec((1, 1, chunk, n), lambda ib, j, ic: (ib, j, cix(ic), 0)),
        state=pl.BlockSpec((1, hb // k, 1, k * p, n), lambda ib, j, ic: (ib, j, cix(ic), 0, 0)),
        final=pl.BlockSpec((1, hb // k, k * p, n), lambda ib, j, ic: (ib, j, 0, 0)),
        smem=pl.BlockSpec(memory_space=pltpu.SMEM),
    )


def _grid(dims):
    return (dims["bsz"], dims["h"] // dims["hb"], dims["nc"])


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_call(xl, dtt, a, bt, ct, *, chunk: int, save_states: bool, interpret: bool):
    dims = _dims(xl, bt, a.shape[0], chunk)
    bsz, h, nc, hb, k, p, n = (dims[key] for key in ("bsz", "h", "nc", "hb", "k", "p", "n"))
    sp = _specs(dims, chunk, reverse=False)
    out_specs = [sp["seq"], sp["final"]]
    out_shape = [jax.ShapeDtypeStruct(xl.shape, xl.dtype),
                 jax.ShapeDtypeStruct((bsz, h // k, k * p, n), jnp.float32)]
    if save_states:
        out_specs.append(sp["state"])
        out_shape.append(jax.ShapeDtypeStruct((bsz, h // k, nc, k * p, n), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, hb=hb, k=k, p=p, save_states=save_states),
        grid=_grid(dims),
        in_specs=[sp["seq"], sp["row"], sp["smem"], sp["group"], sp["group"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb // k, k * p, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(xl, dtt, a, bt, ct)


def _bwd_call(xl, dtt, a, bt, ct, states, dyl, dfinal, *, chunk: int, interpret: bool):
    dims = _dims(xl, bt, a.shape[0], chunk)
    bsz, h, s, hb, k, p, n = (dims[key] for key in ("bsz", "h", "s", "hb", "k", "p", "n"))
    sp = _specs(dims, chunk, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, hb=hb, k=k, p=p),
        grid=_grid(dims),
        in_specs=[sp["seq"], sp["row"], sp["smem"], sp["group"], sp["group"], sp["state"],
                  sp["seq"], sp["final"]],
        out_specs=[sp["seq"], sp["row"], sp["row"], sp["part"], sp["part"]],
        out_shape=[jax.ShapeDtypeStruct(xl.shape, xl.dtype),
                   jax.ShapeDtypeStruct(dtt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dtt.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h // hb, s, n), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h // hb, s, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb // k, k * p, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(xl, dtt, a, bt, ct, states, dyl, dfinal.reshape(bsz, h // k, k * p, n))


def ssd_chunk_scan_fwd(
    x: jax.Array,  # (B, S, H, P) fp32
    dt: jax.Array,  # (B, S, H) fp32 post-softplus
    a: jax.Array,  # (H,) fp32 negative
    b_in: jax.Array,  # (B, S, G, N) fp32 (G divides H)
    c_in: jax.Array,  # (B, S, G, N)
    *,
    chunk: int,
    save_states: bool,
    interpret: bool,
):
    """Forward sweep: y (B, S, H, P), the final state (B, H, P, N) and, with
    ``save_states``, the residuals ``ssd_chunk_scan_bwd`` takes (the inputs
    in kernel layouts and the states entering each chunk), else None."""
    bsz, s, h, p = x.shape
    g = b_in.shape[2]
    if s % chunk or h % g:
        raise ValueError(f"seq {s} must be a multiple of chunk {chunk}, heads {h} of groups {g}")
    hb = _heads_per_block(h // g)
    xl = x.reshape(bsz, s, h * p)  # heads side by side in lanes, as the model lays x out
    # (B, H/hb, nc, hb, L): a block's heads as rows of one (hb, L) tile
    dtt = dt.reshape(bsz, s // chunk, chunk, h // hb, hb).transpose(0, 3, 1, 4, 2)
    bt = b_in.transpose(0, 2, 1, 3)  # (B, G, S, N)
    ct = c_in.transpose(0, 2, 1, 3)
    y, final, *states = _fwd_call(xl, dtt, a, bt, ct, chunk=chunk, save_states=save_states,
                                  interpret=interpret)
    res = (xl, dtt, a, bt, ct, states[0]) if save_states else None
    return y.reshape(x.shape), final.reshape(bsz, h, p, -1), res


def ssd_chunk_scan_bwd(res, dy, dfinal, *, chunk: int, interpret: bool):
    """Reverse sweep: (dx, d dt, d a, dB, dC) in the model's layouts, from
    ``ssd_chunk_scan_fwd``'s residuals and the cotangents of y and of the
    final state."""
    xl, dtt, a, bt, ct, states = res
    bsz, g, s, n = bt.shape
    dxl, ddtt, dlat, dbp, dcp = _bwd_call(xl, dtt, a, bt, ct, states, dy.reshape(xl.shape),
                                          dfinal, chunk=chunk, interpret=interpret)

    def group_sum(part):  # (B, H/hb, S, N) block partials -> (B, S, G, N)
        return part.reshape(bsz, g, -1, s, n).sum(axis=2).transpose(0, 2, 1, 3)

    def unrow(v):  # dt's kernel layout back to (B, S, H)
        return v.transpose(0, 2, 4, 1, 3).reshape(bsz, s, -1)

    da = jnp.sum(dtt * dlat, axis=(0, 2, 4)).reshape(-1)
    return (dxl.reshape(dy.shape), unrow(ddtt), da, group_sum(dbp), group_sum(dcp))
