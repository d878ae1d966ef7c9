"""Pallas kernel validation (interpret=True on CPU) against pure-jnp oracles.

Shape/dtype sweeps + hypothesis property tests per the assignment.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ----------------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,t,h,kh,d,causal,window",
    [
        (1, 128, 128, 4, 4, 64, True, None),  # MHA causal
        (2, 128, 128, 8, 2, 64, True, None),  # GQA 4:1
        (1, 256, 256, 4, 1, 64, True, None),  # MQA
        (1, 128, 128, 2, 2, 64, False, None),  # bidirectional
        (1, 256, 256, 4, 2, 64, True, 64),  # sliding window
        (2, 128, 128, 4, 4, 128, True, None),  # head_dim 128
    ],
)
def test_flash_attention_vs_ref(b, s, t, h, kh, d, causal, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(k1, (b, s, h, d), dtype)
    k = _rand(k2, (b, t, kh, d), dtype)
    v = _rand(k3, (b, t, kh, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window, block_q=64, block_k=64)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    rtol, atol = (2e-2, 2e-2) if dtype == jnp.bfloat16 else (1e-5, 1e-5)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), rtol=rtol, atol=atol
    )


def test_flash_attention_block_shape_independence():
    """Result must not depend on the BlockSpec tiling."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(k1, (1, 256, 4, 64))
    k = _rand(k2, (1, 256, 2, 64))
    v = _rand(k3, (1, 256, 2, 64))
    a = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    b = ops.flash_attention(q, k, v, block_q=128, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@given(
    s=st.sampled_from([64, 128, 192]),
    h=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2]),
    d=st.sampled_from([32, 64]),
    causal=st.booleans(),
)
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(s, h, g, d, causal):
    kh = h
    hq = h * g
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(s + hq + d), 3)
    q = _rand(k1, (1, s, hq, d))
    k = _rand(k2, (1, s, kh, d))
    v = _rand(k3, (1, s, kh, d))
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)
    # attention outputs are convex combinations of v rows
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4


def _flash_grads(attend, q, k, v, do):
    out, vjp = jax.vjp(attend, q, k, v)
    return vjp(do.astype(out.dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "s,h,kh,d,causal,window,blocks,remat",
    [
        (128, 8, 2, 64, True, None, (64, 64), False),  # GQA 4:1, two heads a lane group
        (128, 8, 2, 128, True, None, (64, 64), False),  # GQA 4:1, one head a lane group
        (128, 4, 1, 64, True, None, (64, 64), False),  # MQA
        (256, 4, 2, 64, True, 64, (64, 64), False),  # sliding window
        (128, 4, 2, 64, False, None, (64, 64), False),  # bidirectional
        (256, 8, 2, 64, True, None, (128, 64), False),  # tiling: tall query blocks
        (256, 8, 2, 64, True, 96, (64, 128), False),  # tiling: wide key blocks, window
        (128, 8, 2, 64, True, None, (64, 64), True),  # under jax.checkpoint
    ],
    ids=["gqa-d64", "gqa-d128", "mqa", "window", "bidir", "tiling-q128", "tiling-k128",
         "checkpoint"],
)
def test_flash_attention_grads_vs_ref(s, h, kh, d, causal, window, blocks, remat, dtype):
    """dq, dk and dv of the kernel pair against autodiff of the reference."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(4), 4)
    q = _rand(k1, (2, s, h, d), dtype)
    k = _rand(k2, (2, s, kh, d), dtype)
    v = _rand(k3, (2, s, kh, d), dtype)
    do = _rand(k4, (2, s, h, d))
    block_q, block_k = blocks

    def kernel(q, k, v):
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k)

    attend = jax.checkpoint(kernel) if remat else kernel
    got = jax.jit(functools.partial(_flash_grads, attend))(q, k, v, do)
    expect = _flash_grads(functools.partial(ref.flash_attention_ref, causal=causal, window=window),
                          q, k, v, do)
    rtol, atol = (2e-2, 2e-2) if dtype == jnp.bfloat16 else (2e-5, 2e-5)
    for name, u, w in zip(("dq", "dk", "dv"), got, expect):
        assert u.shape == w.shape and u.dtype == w.dtype, name
        np.testing.assert_allclose(np.asarray(u, np.float32), np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol, err_msg=name)


# ----------------------------------------------------------------------------
# RG-LRU scan
# ----------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,s,n,block_t,block_n",
    [
        (1, 64, 128, 16, 128),
        (2, 128, 256, 16, 128),
        (1, 48, 128, 8, 64),
        (3, 32, 384, 32, 128),
        (2, 64, 640, 16, 128),  # the layout the chip compiles: (16, 128) blocks
    ],
)
def test_rg_lru_vs_ref(b, s, n, block_t, block_n):
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    a = jax.random.uniform(k1, (b, s, n), minval=0.5, maxval=0.999)
    bx = _rand(k2, (b, s, n), scale=0.5)
    out = ops.rg_lru_scan(a, bx, block_t=block_t, block_n=block_n)
    expect = ref.rg_lru_scan_ref(a, bx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-5)


def test_rg_lru_matches_associative_scan():
    """Kernel (linear scan) vs the model's associative_scan path."""
    from repro.models.rglru import rglru_scan_ref

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    a = jax.random.uniform(k1, (2, 64, 128), minval=0.8, maxval=0.999)
    bx = _rand(k2, (2, 64, 128))
    np.testing.assert_allclose(
        np.asarray(ops.rg_lru_scan(a, bx)),
        np.asarray(rglru_scan_ref(a, bx)),
        rtol=1e-5,
        atol=1e-5,
    )


@given(
    s=st.sampled_from([16, 32, 64]),
    n=st.sampled_from([128, 256]),
    decay=st.floats(min_value=0.1, max_value=0.999),
)
@settings(max_examples=10, deadline=None)
def test_rg_lru_property_bounded(s, n, decay):
    # with |a|<1 and bounded inputs, the state stays bounded by |bx|/(1-a)
    key = jax.random.PRNGKey(int(decay * 1000) + s + n)
    a = jnp.full((1, s, n), decay)
    bx = jax.random.uniform(key, (1, s, n), minval=-1.0, maxval=1.0)
    h = ops.rg_lru_scan(a, bx)
    assert float(jnp.max(jnp.abs(h))) <= 1.0 / (1.0 - decay) + 1e-3
    expect = ref.rg_lru_scan_ref(a, bx)
    np.testing.assert_allclose(np.asarray(h), np.asarray(expect), rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------------
# SSD chunk scan
# ----------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,s,h,p,g,n,chunk",
    [
        (1, 64, 2, 32, 1, 16, 16),
        (2, 128, 4, 64, 1, 32, 32),
        (1, 64, 4, 32, 2, 16, 16),  # grouped B/C
        (1, 256, 2, 64, 1, 128, 64),  # larger state
        (2, 128, 4, 64, 1, 128, 64),  # mamba2-370m head layout, chip tiling
    ],
)
def test_ssd_kernel_vs_sequential_ref(b, s, h, p, g, n, chunk):
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    x = _rand(keys[0], (b, s, h, p), scale=0.5)
    dt = jax.random.uniform(keys[1], (b, s, h), minval=0.01, maxval=0.2)
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=-2.0, maxval=1.0))
    b_in = _rand(keys[3], (b, s, g, n), scale=0.5)
    c_in = _rand(keys[4], (b, s, g, n), scale=0.5)
    y, _ = ops.ssd_chunk_scan(x, dt, a, b_in, c_in, chunk=chunk)
    y_ref, _ = ref.ssd_scan_ref(x, dt, a, b_in, c_in)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)


def test_ssd_model_chunked_vs_sequential_ref():
    """models.ssd.ssd_chunked_ref (the train path) vs token-by-token scan."""
    from repro.models.ssd import ssd_chunked_ref

    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    b, s, h, p, g, n = 2, 128, 4, 32, 1, 32
    x = _rand(keys[0], (b, s, h, p), scale=0.5)
    dt = jax.random.uniform(keys[1], (b, s, h), minval=0.01, maxval=0.2)
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=-2.0, maxval=1.0))
    b_in = _rand(keys[3], (b, s, g, n), scale=0.5)
    c_in = _rand(keys[4], (b, s, g, n), scale=0.5)
    y_chunk, h_chunk = ssd_chunked_ref(x, dt, a, b_in, c_in, chunk=32)
    y_seq, h_seq = ref.ssd_scan_ref(x, dt, a, b_in, c_in)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h_seq), rtol=2e-4, atol=2e-4)


def test_ssd_chunk_size_independence():
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    b, s, h, p, g, n = 1, 128, 2, 32, 1, 16
    x = _rand(keys[0], (b, s, h, p), scale=0.5)
    dt = jax.random.uniform(keys[1], (b, s, h), minval=0.01, maxval=0.2)
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=-1.0, maxval=1.0))
    b_in = _rand(keys[3], (b, s, g, n), scale=0.5)
    c_in = _rand(keys[4], (b, s, g, n), scale=0.5)
    y16, _ = ops.ssd_chunk_scan(x, dt, a, b_in, c_in, chunk=16)
    y64, _ = ops.ssd_chunk_scan(x, dt, a, b_in, c_in, chunk=64)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64), rtol=2e-4, atol=2e-4)


def _ssd_inputs(seed, b, s, h, p, g, n):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = _rand(keys[0], (b, s, h, p), scale=0.5)
    dt = jax.random.uniform(keys[1], (b, s, h), minval=0.01, maxval=0.2)
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=-2.0, maxval=1.0))
    b_in = _rand(keys[3], (b, s, g, n), scale=0.5)
    c_in = _rand(keys[4], (b, s, g, n), scale=0.5)
    return x, dt, a, b_in, c_in


@pytest.mark.parametrize(
    "h,g,chunk",
    [
        (2, 1, 16),
        (2, 1, 32),
        (4, 2, 16),  # grouped B/C: dB, dC summed over each group's heads
        (4, 2, 32),
        (64, 1, 16),  # more heads per group than one kernel block takes
    ],
)
def test_ssd_kernel_vjp_vs_refs(h, g, chunk):
    """The backward kernel's (dx, d dt, d a, dB, dC) against autodiff of the
    chunked XLA scan and of the token-by-token recurrence, with cotangents
    on both the output and the final state."""
    from repro.models.ssd import ssd_chunked_ref

    p, n = (8, 8) if h > 8 else (32, 16)
    args = _ssd_inputs(7, 2, 64, h, p, g, n)
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    dy = _rand(k1, (2, 64, h, p))
    dfinal = _rand(k2, (2, h, p, n), scale=0.1)
    _, vjp = jax.vjp(lambda *z: ops.ssd_chunk_scan(*z, chunk=chunk), *args)
    got = vjp((dy, dfinal))
    with jax.default_matmul_precision("highest"):
        for reference in (lambda *z: ssd_chunked_ref(*z, chunk=chunk), ref.ssd_scan_ref):
            _, ref_vjp = jax.vjp(reference, *args)
            for name, u, v in zip(("dx", "ddt", "da", "dB", "dC"), got, ref_vjp((dy, dfinal))):
                assert u.shape == v.shape, name
                np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=2e-4, atol=2e-4,
                                           err_msg=name)


def test_ssd_kernel_final_state_vs_chunked_ref():
    from repro.models.ssd import ssd_chunked_ref

    args = _ssd_inputs(9, 2, 128, 4, 32, 1, 16)
    y, final = ops.ssd_chunk_scan(*args, chunk=32)
    with jax.default_matmul_precision("highest"):
        y_ref, final_ref = ssd_chunked_ref(*args, chunk=32)
    np.testing.assert_allclose(np.asarray(final), np.asarray(final_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)


def test_ssd_kernel_only_in_unsharded_steps():
    """A step sharded over a mesh keeps the XLA scan: Mosaic kernels cannot
    be partitioned automatically."""
    from repro.configs import get_smoke_config
    from repro.dist.train import with_act_sharding
    from repro.models import lm_init, lm_loss

    cfg = get_smoke_config("mamba2-370m").scaled(use_scan_kernels=True)
    params = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for step_cfg, kernels in ((cfg, True), (with_act_sharding(cfg, mesh), False)):
        with mesh:
            jaxpr = jax.make_jaxpr(lambda p, b: lm_loss(step_cfg, p, b)[0])(params, batch)
        assert ("pallas_call" in str(jaxpr)) == kernels


@pytest.mark.parametrize("head_dim", [64, 128])
def test_gqa_flash_kernel_matches_xla_path(head_dim):
    """gqa_apply at SMOKE widths: the flash kernel pair gives the query-chunked
    XLA path's output and parameter gradients within bfloat16 tolerance, and
    a step sharded over a mesh (``act_pspec`` set) keeps the XLA path."""
    from repro.models.attention import gqa_apply, gqa_init

    params = gqa_init(jax.random.PRNGKey(0), 128, 4, 2, head_dim)
    x = _rand(jax.random.PRNGKey(1), (2, 256, 128), jnp.bfloat16)
    weight = _rand(jax.random.PRNGKey(2), (2, 256, 128))

    def loss(p, x, flash, act_pspec=None):
        out = gqa_apply(p, x, n_heads=4, n_kv_heads=2, head_dim=head_dim, rope_theta=1e6,
                        chunk_q=64, use_flash_kernel=flash, act_pspec=act_pspec)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    def run(flash):
        return jax.jit(jax.value_and_grad(lambda p, x: loss(p, x, flash), has_aux=True))(params, x)

    (_, out), grads = run(True)
    (_, want_out), want_grads = run(False)
    for name, u, w in [("out", out, want_out), *((n, grads[n], want_grads[n]) for n in want_grads)]:
        u, w = np.asarray(u, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(u - w) <= 2e-2 * np.linalg.norm(w), name
        np.testing.assert_allclose(u, w, rtol=0, atol=2e-2 * np.max(np.abs(w)), err_msg=name)

    def step_jaxpr(act_pspec):
        return str(jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x, True, act_pspec)[0]))(params, x))

    assert step_jaxpr(None).count("flash_attention_") == 3  # forward, dK/dV, dQ
    with jax.make_mesh((1, 1), ("data", "model")):
        sharded = step_jaxpr(("data", None))
    assert "flash_attention" not in sharded and "pallas_call" not in sharded


def test_ssd_kernel_exp_within_two_ulp():
    """The SSD kernels' own exp (Mosaic's is off by tens of ulp on a v5e)
    against float64 over the decays' range."""
    from repro.kernels.ssd import _exp

    x = -np.linspace(0.0, 87.0, 200_003, dtype=np.float32)
    got = np.asarray(jax.jit(_exp)(x), np.float64)
    want = np.exp(x.astype(np.float64))
    assert np.all(np.abs(got - want) <= 2 * np.spacing(want.astype(np.float32)))
