"""Pallas TPU flash attention (causal / sliding-window, GQA): a forward
kernel and the two backward kernels of its gradient.

TPU adaptation of FlashAttention-2 (arXiv:2205.14135, arXiv:2307.08691):
the online softmax accumulates in VMEM scratch across the sequential
minor-most grid dimension, q, k and v reach the MXU in their own dtype
with float32 accumulation, and the probabilities are cast to v's dtype for
P V.  Block pairs that are fully masked (the causal upper triangle, the
band outside a sliding window) are skipped, and the key/value index maps
are clamped to the blocks a query block can see, so a skipped block is not
fetched either; only blocks that straddle the mask's edge build a mask.

Layout: q, o and their cotangents stay in the model's (B, S, H d) layout,
k and v in (B, T, K d), heads side by side in lanes, so no transpose runs
around the kernels.  A grid row takes c KV heads and the c G query heads
that read them: c = 128 // d when heads are narrower than the 128 lanes,
so that the row's K/V block fills one lane tile (two heads at d = 64).
The query block is then G lane chunks of c heads each.  Every matmul runs
on whole lane chunks: a query head is its chunk with the other heads'
lanes zeroed, and K/V are rotated so that its KV head sits in its lanes.
On the MXU that costs what a d = 128 head costs.

Under differentiation the forward kernel also writes the per-row
log-sum-exp (float32, (B, K / c, c G, S)).  The backward recomputes the
probabilities from it: a dK/dV kernel gridded over key blocks sweeps the
query blocks that see each one and sums over the query heads that share a
KV head in VMEM, and a dQ kernel sweeps the key blocks each query block
sees.  ``delta = rowsum(dO o)`` is computed outside the kernels.  No score
block reaches HBM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# Preferred block edges (query rows, key rows) of the forward kernel and of
# the two backward kernels, timed on a v5e at 4 x 4096 tokens, d = 128 and
# 1 x 16384, d = 64: wider key blocks halve the forward's time, and no
# other shape beats 512 x 512 in the backward.
FWD_BLOCKS = (512, 1024)
BWD_BLOCKS = (512, 512)


class Spec(NamedTuple):
    """The static settings of one attention call."""

    heads: int
    kv_heads: int
    head_dim: int
    scale: float
    causal: bool
    window: Optional[int]
    block_q: int
    block_k: int

    @property
    def group(self) -> int:  # query heads per KV head
        return self.heads // self.kv_heads

    @property
    def kv_per_row(self) -> int:
        """KV heads one grid row takes: enough to fill the 128 lanes."""
        c = max(1, LANES // self.head_dim)
        if self.kv_heads % c == 0 and (c * self.head_dim) % LANES == 0:
            return c
        return 1 if self.head_dim % LANES == 0 else self.kv_heads

    @property
    def width(self) -> int:  # lanes of one row's K/V block and of one query chunk
        return self.kv_per_row * self.head_dim


def default_block(n: int, preferred: int) -> int:
    """The largest power-of-two fraction of ``preferred``, down to 128 rows,
    that divides a sequence of n, else n."""
    b = preferred
    while b >= LANES:
        if n % b == 0:
            return b
        b //= 2
    return n


def _heads(spec: Spec):
    """(query head in the row, its lane chunk, its slot in the chunk, its
    KV head in the row) for every query head of a grid row."""
    c, g = spec.kv_per_row, spec.group
    return [(h, h // c, h % c, h // g) for h in range(c * g)]


def _nt(a, b):  # a @ b.T
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _nn(a, b):  # a @ b
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


class _Lanes:
    """Slots and rotations within a lane chunk of c heads."""

    def __init__(self, spec: Spec):
        self.c, self.d, self.w = spec.kv_per_row, spec.head_dim, spec.width
        self.slot = jax.lax.broadcasted_iota(jnp.int32, (1, self.w), 1) // self.d

    def only(self, x, e):
        """x with every lane outside slot e zeroed."""
        return x if self.c == 1 else jnp.where(self.slot == e, x, jnp.zeros_like(x))

    def rotate(self, x, slots):
        """x moved ``slots`` head slots up its lanes (slot i to i + slots)."""
        shift = (slots % self.c) * self.d
        if not shift:
            return x
        if x.dtype.itemsize == 4:
            return pltpu.roll(x, shift, 1)
        # Mosaic rotates 32-bit data only; the round trip is exact.
        return pltpu.roll(x.astype(jnp.float32), shift, 1).astype(x.dtype)

    def rotations(self, x):
        """x rotated by every number of slots, indexed by that number."""
        return [self.rotate(x, r) for r in range(self.c)]

    def merge(self, parts):
        """One chunk from c arrays, taking slot e from parts[e]."""
        out = parts[0]
        for e in range(1, self.c):
            out = jnp.where(self.slot == e, parts[e], out)
        return out


def _block_state(q0, k0, spec: Spec, bq: int, bk: int):
    """Whether a (query block, key block) pair holds a visible pair, and
    whether it holds a masked one."""
    visible, partial = jnp.asarray(True), jnp.asarray(False)
    if spec.causal:
        visible = visible & (k0 <= q0 + bq - 1)
        partial = partial | (k0 + bk - 1 > q0)
    if spec.window is not None:
        visible = visible & (k0 + bk - 1 > q0 - spec.window)
        partial = partial | (k0 <= q0 + bq - 1 - spec.window)
    return visible, partial


def _allowed(qpos, kpos, spec: Spec):
    ok = jnp.ones(qpos.shape, jnp.bool_)
    if spec.causal:
        ok = ok & (kpos <= qpos)
    if spec.window is not None:
        ok = ok & (kpos > qpos - spec.window)
    return ok


def _mask(q0, k0, spec: Spec, shape, q_axis: int):
    """The allowed (query, key) pairs of a block, queries along ``q_axis``."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return _allowed(qpos, kpos, spec)


def _when_visible(visible, partial, body):
    """Run body(masked) on a visible block pair, masking only where the
    pair straddles the mask's edge."""
    pl.when(visible & jnp.logical_not(partial))(lambda: body(False))
    pl.when(visible & partial)(lambda: body(True))


def _key_range(iq, spec: Spec, bq: int, bk: int, nk: int):
    """The first and last key blocks that query block iq sees."""
    lo, hi = 0, nk - 1
    if spec.causal:
        hi = jnp.minimum(hi, (iq * bq + bq - 1) // bk)
    if spec.window is not None:
        lo = jnp.maximum(0, (iq * bq - spec.window + 1) // bk)
    return lo, hi


def _query_range(ik, spec: Spec, bq: int, bk: int, nq: int):
    """The first and last query blocks that see key block ik."""
    lo, hi = 0, nq - 1
    if spec.causal:
        lo = (ik * bk) // bq
    if spec.window is not None:
        hi = jnp.minimum(hi, (ik * bk + bk + spec.window - 2) // bq)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _params(n_parallel: int):
    # Above the 16 MiB default: blocks of 1024 rows need up to 29 MiB (v5e).
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",),
        vmem_limit_bytes=64 * 2**20)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *refs, spec: Spec, with_lse: bool):
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        (m_ref, l_ref, acc_ref), lse_ref = refs, None
    bq, bk, w = spec.block_q, spec.block_k, spec.width
    iq, ik = pl.program_id(2), pl.program_id(3)
    lanes = _Lanes(spec)
    heads = _heads(spec)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0, k0 = iq * bq, ik * bk

    def body(masked: bool):
        ks, vs = lanes.rotations(k_ref[0]), lanes.rotations(v_ref[0])
        ok = _mask(q0, k0, spec, (bq, bk), 0) if masked else None
        for h, chunk, e, f in heads:
            r = (e - f) % lanes.c  # the rotation that puts KV head f in slot e
            q = lanes.only(q_ref[0, :, chunk * w:(chunk + 1) * w], e)
            s = _nt(q, ks[r]) * spec.scale  # (bq, bk)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]  # (bq, 128), the same in every lane
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            if masked:  # a row with no key seen yet has m_new = NEG_INF
                p = jnp.where(ok, p, 0.0)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            pv = _nn(p.astype(vs[r].dtype), vs[r])  # slot e holds this head
            acc_ref[h] = acc_ref[h] * alpha[:, :1] + pv

    _when_visible(*_block_state(q0, k0, spec, bq, bk), body)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        outs = []
        for h, chunk, e, f in heads:
            l = l_ref[h]
            outs.append(acc_ref[h] / jnp.where(l == 0.0, 1.0, l)[:, :1])
        for chunk in range(spec.group):
            o = lanes.merge(outs[chunk * lanes.c:(chunk + 1) * lanes.c])
            o_ref[0, :, chunk * w:(chunk + 1) * w] = o.astype(o_ref.dtype)
        if with_lse:
            rows = [(m_ref[h] + jnp.log(l_ref[h])).T[:1] for h, *_ in heads]  # (1, bq) each
            lse_ref[0, 0] = jnp.concatenate(rows, axis=0)


def flash_attention_fwd(q, k, v, spec: Spec, *, with_lse: bool, interpret: bool):
    """o (B, S, H d), and with ``with_lse`` the log-sum-exp of every query
    row (B, K / c, c G, S) float32, from q (B, S, H d) and k, v (B, T, K d)."""
    b, s, _ = q.shape
    t = k.shape[1]
    bq, bk, w = spec.block_q, spec.block_k, spec.width
    if s % bq or t % bk:
        raise ValueError(f"blocks ({bq}, {bk}) do not divide the sequences ({s}, {t})")
    nq, nk = s // bq, t // bk
    c, g = spec.kv_per_row, spec.group
    rows = spec.kv_heads // c

    def q_index(ib, j, iq, ik):
        return (ib, iq, j)

    def kv_index(ib, j, iq, ik):
        return (ib, _clamp(ik, *_key_range(iq, spec, bq, bk, nk)), j)

    out_specs = [pl.BlockSpec((1, bq, g * w), q_index)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, c * g, bq), lambda ib, j, iq, ik: (ib, j, 0, iq)))
        out_shape.append(jax.ShapeDtypeStruct((b, rows, c * g, s), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec, with_lse=with_lse),
        grid=(b, rows, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, g * w), q_index),
            pl.BlockSpec((1, bk, w), kv_index),
            pl.BlockSpec((1, bk, w), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((c * g, bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((c * g, bq, LANES), jnp.float32),  # running sum
            pltpu.VMEM((c * g, bq, w), jnp.float32),  # unnormalised output
        ],
        compiler_params=_params(3),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return tuple(out) if with_lse else (out[0], None)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, spec: Spec):
    """One key block of c KV heads; the sweep runs over the query blocks.
    Scores are held transposed, (bk, bq), so the log-sum-exp and delta of
    the query rows are row vectors.  dk_acc and dv_acc hold each head's
    contribution in its own query slot, summed by the rotation (f - e)
    that carries slot e to its KV head's slot f."""
    bq, bk, w = spec.block_q, spec.block_k, spec.width
    ik, iq = pl.program_id(2), pl.program_id(3)
    lanes = _Lanes(spec)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q0, k0 = iq * bq, ik * bk

    def body(masked: bool):
        ks, vs = lanes.rotations(k_ref[0]), lanes.rotations(v_ref[0])
        ok = _mask(q0, k0, spec, (bk, bq), 1) if masked else None
        for h, chunk, e, f in _heads(spec):
            lo, hi = chunk * w, (chunk + 1) * w
            q = lanes.only(q_ref[0, :, lo:hi], e)
            do = lanes.only(do_ref[0, :, lo:hi], e)
            s = _nt(ks[(e - f) % lanes.c], q) * spec.scale  # (bk, bq)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, 0, h:h + 1, :])
            dp = _nt(vs[(e - f) % lanes.c], do)
            ds = p * (dp - delta_ref[0, 0, h:h + 1, :])
            r = (f - e) % lanes.c  # the rotation that carries slot e to slot f
            dv_acc[r] = dv_acc[r] + _nn(p.astype(do.dtype), do)
            dk_acc[r] = dk_acc[r] + _nn(ds.astype(q.dtype), q)

    _when_visible(*_block_state(q0, k0, spec, bq, bk), body)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _finalize():
        dk = sum(lanes.rotate(dk_acc[r], r) for r in range(lanes.c)) * spec.scale
        dv = sum(lanes.rotate(dv_acc[r], r) for r in range(lanes.c))
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               lse_col, delta_col, dq_acc, *, spec: Spec):
    """One query block of c G heads; the sweep runs over the key blocks.
    The rows' log-sum-exp and delta are turned into lane-replicated columns
    once, at the start of the sweep."""
    bq, bk, w = spec.block_q, spec.block_k, spec.width
    iq, ik = pl.program_id(2), pl.program_id(3)
    lanes = _Lanes(spec)
    heads = _heads(spec)

    @pl.when(ik == 0)
    def _init():
        for h, *_ in heads:
            lse_col[h] = jnp.broadcast_to(lse_ref[0, 0, h:h + 1, :], (LANES, bq)).T
            delta_col[h] = jnp.broadcast_to(delta_ref[0, 0, h:h + 1, :], (LANES, bq)).T
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q0, k0 = iq * bq, ik * bk

    def body(masked: bool):
        ks, vs = lanes.rotations(k_ref[0]), lanes.rotations(v_ref[0])
        ok = _mask(q0, k0, spec, (bq, bk), 0) if masked else None
        for h, chunk, e, f in heads:
            lo, hi = chunk * w, (chunk + 1) * w
            r = (e - f) % lanes.c
            q = lanes.only(q_ref[0, :, lo:hi], e)
            do = lanes.only(do_ref[0, :, lo:hi], e)
            s = _nt(q, ks[r]) * spec.scale  # (bq, bk)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            p = jnp.exp(s - lse_col[h][:, :1])
            dp = _nt(do, vs[r])
            ds = p * (dp - delta_col[h][:, :1])
            dq_acc[h] = dq_acc[h] + _nn(ds.astype(ks[r].dtype), ks[r])  # slot e holds this head

    _when_visible(*_block_state(q0, k0, spec, bq, bk), body)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        for chunk in range(spec.group):
            parts = [dq_acc[chunk * lanes.c + e] for e in range(lanes.c)]
            dq = lanes.merge(parts) * spec.scale
            dq_ref[0, :, chunk * w:(chunk + 1) * w] = dq.astype(dq_ref.dtype)


def _row_specs(spec: Spec):
    """BlockSpec makers for a query block (of q, o, dO, dq), a K/V block
    (of k, v, dk, dv) and a block of rows of the log-sum-exp and delta."""
    c, g, w = spec.kv_per_row, spec.group, spec.width
    return (lambda index: pl.BlockSpec((1, spec.block_q, g * w), index),
            lambda index: pl.BlockSpec((1, spec.block_k, w), index),
            lambda index: pl.BlockSpec((1, 1, c * g, spec.block_q), index))


def flash_attention_bwd_dkv(q, k, v, do, lse, delta, spec: Spec, *, interpret: bool):
    """(dk, dv) on a grid of (batch, row, key block, query block)."""
    b, s, _ = q.shape
    bq, bk = spec.block_q, spec.block_k
    nq, nk = s // bq, k.shape[1] // bk
    q_at, kv_at, rows_at = _row_specs(spec)

    def q_index(ib, j, ik, iq):
        return (ib, _clamp(iq, *_query_range(ik, spec, bq, bk, nq)), j)

    def rows_index(ib, j, ik, iq):
        return (ib, j, 0, _clamp(iq, *_query_range(ik, spec, bq, bk, nq)))

    def kv_index(ib, j, ik, iq):
        return (ib, ik, j)

    acc = pltpu.VMEM((spec.kv_per_row, bk, spec.width), jnp.float32)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, spec=spec),
        grid=(b, spec.kv_heads // spec.kv_per_row, nk, nq),
        in_specs=[q_at(q_index), kv_at(kv_index), kv_at(kv_index), q_at(q_index),
                  rows_at(rows_index), rows_at(rows_index)],
        out_specs=[kv_at(kv_index), kv_at(kv_index)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[acc, acc],
        compiler_params=_params(3),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)


def flash_attention_bwd_dq(q, k, v, do, lse, delta, spec: Spec, *, interpret: bool):
    """dq on a grid of (batch, row, query block, key block)."""
    b, s, _ = q.shape
    bq, bk = spec.block_q, spec.block_k
    nq, nk = s // bq, k.shape[1] // bk
    n = spec.kv_per_row * spec.group
    q_at, kv_at, rows_at = _row_specs(spec)

    def q_index(ib, j, iq, ik):
        return (ib, iq, j)

    def rows_index(ib, j, iq, ik):
        return (ib, j, 0, iq)

    def kv_index(ib, j, iq, ik):
        return (ib, _clamp(ik, *_key_range(iq, spec, bq, bk, nk)), j)

    return pl.pallas_call(
        functools.partial(_dq_kernel, spec=spec),
        grid=(b, spec.kv_heads // spec.kv_per_row, nq, nk),
        in_specs=[q_at(q_index), kv_at(kv_index), kv_at(kv_index), q_at(q_index),
                  rows_at(rows_index), rows_at(rows_index)],
        out_specs=q_at(q_index),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((n, bq, LANES), jnp.float32),  # log-sum-exp columns
            pltpu.VMEM((n, bq, LANES), jnp.float32),  # delta columns
            pltpu.VMEM((n, bq, spec.width), jnp.float32),
        ],
        compiler_params=_params(3),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)


def flash_attention_bwd(q, k, v, o, lse, do, spec: Spec, *, interpret: bool):
    """(dq, dk, dv) in the layouts and dtypes of q, k and v."""
    b, s, _ = q.shape
    c = spec.kv_per_row
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, s, spec.heads, spec.head_dim), axis=-1)  # (B, S, H)
    delta = delta.transpose(0, 2, 1).reshape(b, spec.kv_heads // c, c * spec.group, s)
    dk, dv = flash_attention_bwd_dkv(q, k, v, do, lse, delta, spec, interpret=interpret)
    dq = flash_attention_bwd_dq(q, k, v, do, lse, delta, spec, interpret=interpret)
    return dq, dk, dv
