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
all heads of the block.

The decay terms are built once per grid step for the whole block, lane
dense: dt arrives as the block's (hb, L) tile, one row a head, and log a,
its cumsum (a product with the triangle), decay_in, decay_out and the
chunk decay are taken over those rows, one ``_exp`` over a few vregs each.
Built head by head as (L, 1) columns they cost a whole vreg per 8 values,
and each column took a masked lane reduction; one transpose of the rows
gives every head's columns, and a lane gather spreads a lane group's
columns over its lanes in one step.  The one exp left per head is its
(L, L) decay tile, and a lane group's k tiles sit side by side in one
(L, kL) tile, so that y_diag and d scores are single products over it.
The backward kernel reduces its per-head terms of d log a and d dt over
each head's lanes by a transpose and sublane sums, into (hb, L) rows, and
finishes both rows for the block at once.

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


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _tri(chunk):
    """(L, L) float 0/1: row i holds 1 at lanes j <= i."""
    return (_iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)).astype(jnp.float32)


def _block_rows(dt, a):
    """The block's decays within the chunk as (hb, L) rows, one a head, from
    its dt rows and its decay rates a as an (hb, 1) column: dt, decay_in =
    exp(acs), decay_out = exp(total - acs) and acs, the inclusive cumsum of
    log a (a product with the triangle, exact at HIGHEST); total is acs's
    last lane, so decay_in's last lane is the chunk decay exp(total)."""
    chunk = dt.shape[1]
    acs = _mm_nt(dt * a, _tri(chunk))
    return dt, _exp(acs), _exp(acs[:, chunk - 1:] - acs), acs


# Quantities of the block's columns tile, in its order.
_DT, _IN, _OUT, _ACS = range(4)


def _take(src, idx):
    """src[r, idx[r, c]]: a lane gather at idx's shape, src padded or idx
    widened so that the two match, as Mosaic's gather needs."""
    rows, w = idx.shape
    src = jnp.broadcast_to(src, (rows, src.shape[1]))
    if src.shape[1] < w:
        src = jnp.concatenate([src, jnp.zeros((rows, w - src.shape[1]), src.dtype)], axis=1)
    elif src.shape[1] > w:
        idx = jnp.concatenate([idx, jnp.zeros((rows, src.shape[1] - w), idx.dtype)], axis=1)
    return jnp.take_along_axis(src, idx, axis=1, mode="promise_in_bounds")[:, :w]


class _Group(NamedTuple):
    dt: jax.Array  # (L, kP): each head's dt column over its P lanes
    decay_in: jax.Array  # (L, kP) exp(acs)
    decay_out: jax.Array  # (L, kP) exp(total - acs)
    chunk_decay: jax.Array  # (kP, N): exp(total) over each head's state rows
    decay: jax.Array  # (L, kL): the k heads' masked exp(acs_i - acs_j) side by side


def _group_terms(cols, q, *, hb, k, p, n):
    """Lane group ``q``'s decays, from the block's (L, 4 hb) columns tile
    (``_block_rows`` transposed): one lane gather spreads each quantity
    over the group's lanes, and the group's k decay tiles take one exp."""
    chunk = cols.shape[0]
    head0 = q * k

    def spread(what, width, unit):
        return _take(cols, what * hb + head0 + _iota((chunk, width), 1) // unit)

    acs = spread(_ACS, k * chunk, chunk)  # (L, kL): acs_i of head j in its L lanes
    i, j = _iota(acs.shape, 0), _iota(acs.shape, 1) % chunk
    acs_j = jnp.sum(jnp.where(i == j, acs, 0.0), axis=0, keepdims=True)  # (1, kL): acs_j, exact
    # The chunk decay is decay_in's last row; a row of the state takes its head's.
    last = cols[chunk - 1:]
    return _Group(spread(_DT, k * p, p), spread(_IN, k * p, p), spread(_OUT, k * p, p),
                  _take(last, _IN * hb + head0 + _iota((k * p, n), 0) // p),
                  jnp.where(i >= j, _exp(acs - acs_j), 0.0))


def _by_head(v, k, p):
    """(L, kP) -> (kL, kP): k copies of v stacked, copy j kept to head j's
    lanes, so a product with the group's (L, kL) decay tile keeps heads apart."""
    head = _iota(v.shape, 1) // p
    return jnp.concatenate([jnp.where(head == j, v, 0.0) for j in range(k)], axis=0)


def _head_sums(v, k, width):
    """(L, k width) -> (k, L): row j sums head j's lanes, through one
    transpose and sublane sums instead of a masked lane reduction a head."""
    t = v.T
    return jnp.concatenate([jnp.sum(t[j * width:(j + 1) * width], axis=0, keepdims=True)
                            for j in range(k)], axis=0)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, *refs, chunk, hb, k, p, save_states):
    if save_states:
        y_ref, final_ref, states_ref, h_scratch = refs
    else:
        (y_ref, final_ref, h_scratch), states_ref = refs, None
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    b = b_ref[0, 0]  # (L, N)
    c = c_ref[0, 0]  # (L, N)
    n = b.shape[1]
    # C B^T, shared by the block's heads, once for each head of a lane group: (L, kL)
    cbk = _mm_nt(c, jnp.concatenate([b] * k, axis=0))
    cols = jnp.concatenate(_block_rows(dt_ref[0, 0, 0], a_ref[0]), axis=0).T  # (L, 4 hb)
    w = k * p

    def group(q, carry):
        t = _group_terms(cols, q, hb=hb, k=k, p=p, n=n)
        off = pl.multiple_of(q * w, w)
        xdt = x_ref[0, :, pl.ds(off, w)] * t.dt  # (L, kP)
        h = h_scratch[q]  # (kP, N): the k heads' states entering this chunk
        # y_diag = scores @ xdt for the k heads in one product over their side-by-side tiles
        y = t.decay_in * _mm_nt(c, h) + _mm(cbk * t.decay, _by_head(xdt, k, p))
        y_ref[0, :, pl.ds(off, w)] = y.astype(y_ref.dtype)
        if save_states:
            states_ref[0, q, 0] = h
        h_scratch[q] = t.chunk_decay * h + _mm_tn(xdt * t.decay_out, b)
        return carry

    jax.lax.fori_loop(0, hb // k, group, 0)

    @pl.when(ic == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = h_scratch[...]


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, states_ref, dy_ref, dfinal_ref,
                dx_ref, ddt_ref, dla_ref, db_ref, dc_ref, dh_scratch, acs_rows, out_rows,
                dt_rows, gh_rows, *, chunk, hb, k, p):
    @pl.when(pl.program_id(2) == 0)  # the last chunk comes first
    def _init():
        dh_scratch[...] = dfinal_ref[0]

    b = b_ref[0, 0]  # (L, N)
    c = c_ref[0, 0]
    n = b.shape[1]
    cbk = _mm_nt(c, jnp.concatenate([b] * k, axis=0))  # (L, kL)
    a = a_ref[0]  # (hb, 1)
    rows = _block_rows(dt_ref[0, 0, 0], a)
    cols = jnp.concatenate(rows, axis=0).T
    w = k * p

    def group(q, carry):
        db, dc, d_cbk = carry
        t = _group_terms(cols, q, hb=hb, k=k, p=p, n=n)
        off = pl.multiple_of(q * w, w)
        x = x_ref[0, :, pl.ds(off, w)]  # (L, kP)
        dy = dy_ref[0, :, pl.ds(off, w)]
        xdt = x * t.dt
        h = states_ref[0, q, 0]  # (kP, N): the states entering this chunk
        g = dh_scratch[q]  # (kP, N): d loss / d the states leaving it
        bg = _mm_nt(b, g)  # (L, kP)
        # y_diag = scores @ xdt; scores = cb * decay, the k heads' tiles side by side
        scores = cbk * t.decay  # (L, kL)
        d_scores = _mm_nt(dy, _by_head(xdt, k, p))  # (L, kL)
        # from h' = chunk_decay h + xdt^T @ (B * decay_out), and y_diag: each
        # head's scores^T @ dy, its tile stacked over dy kept to its lanes
        stacked = jnp.concatenate([scores[:, j * chunk:(j + 1) * chunk] for j in range(k)], axis=0)
        dxdt = t.decay_out * bg + _mm_tn(stacked, _by_head(dy, k, p))
        d_cbk = d_cbk + d_scores * t.decay  # dB, dC from it after the loop, once for all heads
        # d acs_i gets each row sum of m and loses each column sum; y_off =
        # decay_in * (C @ h^T) adds d_in.  Through decay_out, h' takes
        # d_out from d acs, which the chunk total gives back (after the loop).
        m = d_scores * scores
        d_in = dy * t.decay_in * _mm_nt(c, h)
        if p == chunk:  # the same lanes a head: one transpose for both
            d_acs = _head_sums(m + d_in, k, chunk)
        else:
            d_acs = _head_sums(m, k, chunk) + _head_sums(d_in, k, p)
        col_sums = jnp.sum(m, axis=0, keepdims=True)  # (1, kL)
        d_acs = d_acs - jnp.concatenate(
            [col_sums[:, j * chunk:(j + 1) * chunk] for j in range(k)], axis=0)
        heads = pl.ds(q * k, k)
        acs_rows[heads, :] = d_acs  # (k, L)
        out_rows[heads, :] = _head_sums(xdt * bg * t.decay_out, k, p)  # d_out
        dt_rows[heads, :] = _head_sums(dxdt * x, k, p)  # xdt = x * dt
        gh = g * h
        gh_rows[heads, :] = jnp.concatenate(
            [jnp.sum(gh[j * p:(j + 1) * p], axis=0, keepdims=True) for j in range(k)], axis=0)
        dy_in = dy * t.decay_in
        dc = dc + _mm(dy_in, h)
        db = db + _mm(xdt * t.decay_out, g)
        dx_ref[0, :, pl.ds(off, w)] = (dxdt * t.dt).astype(dx_ref.dtype)
        dh_scratch[q] = _mm_tn(dy_in, c) + t.chunk_decay * g
        return db, dc, d_cbk

    zeros = jnp.zeros(b.shape, jnp.float32)
    db, dc, d_cbk = jax.lax.fori_loop(0, hb // k, group, (zeros, zeros, jnp.zeros_like(cbk)))
    d_cb = sum(d_cbk[:, j * chunk:(j + 1) * chunk] for j in range(k))  # (L, L)
    db_ref[0, 0] = db + _mm_tn(d_cb, c)
    dc_ref[0, 0] = dc + _mm(d_cb, b)
    # The block's d log a as (hb, L) rows: acs = cumsum(log_a) takes the
    # reverse cumsum of d acs.  total = sum(log_a) takes chunk_decay * (dh' . h)
    # and the sum of d_out, whose lanes from l on cancel d_out in d acs's
    # reverse cumsum at l: what is left at l is d_out's sum over lanes < l.
    tri = _tri(chunk)
    d_total = rows[_IN][:, chunk - 1:] * jnp.sum(gh_rows[...], axis=1, keepdims=True)
    dla = _mm(jnp.concatenate([acs_rows[...], out_rows[...]], axis=1),
              jnp.concatenate([tri, 1.0 - tri], axis=0)) + d_total
    # log_a = dt * a and xdt = x * dt
    ddt_ref[0, 0, 0] = dt_rows[...] + a * dla
    dla_ref[0, 0, 0] = dla


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
        head=pl.BlockSpec((1, hb, 1), lambda ib, j, ic: (j, 0, 0)),
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
        in_specs=[sp["seq"], sp["row"], sp["head"], sp["group"], sp["group"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb // k, k * p, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(xl, dtt, a.reshape(h // hb, hb, 1), bt, ct)


def _bwd_call(xl, dtt, a, bt, ct, states, dyl, dfinal, *, chunk: int, interpret: bool):
    dims = _dims(xl, bt, a.shape[0], chunk)
    bsz, h, s, hb, k, p, n = (dims[key] for key in ("bsz", "h", "s", "hb", "k", "p", "n"))
    sp = _specs(dims, chunk, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, hb=hb, k=k, p=p),
        grid=_grid(dims),
        in_specs=[sp["seq"], sp["row"], sp["head"], sp["group"], sp["group"], sp["state"],
                  sp["seq"], sp["final"]],
        out_specs=[sp["seq"], sp["row"], sp["row"], sp["part"], sp["part"]],
        out_shape=[jax.ShapeDtypeStruct(xl.shape, xl.dtype),
                   jax.ShapeDtypeStruct(dtt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dtt.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h // hb, s, n), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h // hb, s, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb // k, k * p, n), jnp.float32),
                        *(pltpu.VMEM((hb, chunk), jnp.float32) for _ in range(3)),
                        pltpu.VMEM((hb, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(xl, dtt, a.reshape(h // hb, hb, 1), bt, ct, states, dyl,
      dfinal.reshape(bsz, h // k, k * p, n))


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
