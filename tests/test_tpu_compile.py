"""Compile the Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler, which is installed with jax, lowers each
kernel for a ``v5e:2x2`` topology that is described, not attached, and
refuses what the chip's compiler would refuse (unaligned blocks, primitives
Mosaic cannot lower, too much VMEM).  Each compile must contain the Mosaic
kernel (``tpu_custom_call``), so a silent fallback to XLA cannot pass.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (
    BWD_BLOCKS,
    FWD_BLOCKS,
    Spec,
    default_block,
    flash_attention_bwd_dkv,
    flash_attention_bwd_dq,
    flash_attention_fwd,
)
from repro.kernels.rg_lru import rg_lru_scan_blocked
from repro.kernels.ssd import ssd_chunk_scan_bwd, ssd_chunk_scan_fwd

SEQ = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _flash_kernel(kernel, spec):
    """One of the flash kernels as a function of its arrays."""
    if kernel == "fwd":
        return lambda q, k, v: flash_attention_fwd(q, k, v, spec, with_lse=False, interpret=False)[0]
    if kernel == "fwd_lse":
        return functools.partial(flash_attention_fwd, spec=spec, with_lse=True, interpret=False)
    bwd = {"bwd_dkv": flash_attention_bwd_dkv, "bwd_dq": flash_attention_bwd_dq}[kernel]
    return functools.partial(bwd, spec=spec, interpret=False)


_FLASH_SHAPES = {
    "gqa": (1, SEQ, 32, 8, 128, None),  # mistral-nemo-12b: GQA 4:1, causal
    "windowed": (1, SEQ, 16, 8, 256, 1024),  # gemma3-12b local layers: sliding window
    "gqa-d64": (1, 4 * SEQ, 32, 8, 64, None),  # granite-4.0-h-micro: two heads a lane group
}


@pytest.mark.parametrize(
    "shape,kernel",
    [("gqa", "fwd"), ("windowed", "fwd"),
     *((shape, kernel) for shape in ("gqa", "gqa-d64")
       for kernel in ("fwd_lse", "bwd_dkv", "bwd_dq")),
     ("gqa-d64", "fwd")],
    ids=["gqa", "windowed", "gqa-fwd-lse", "gqa-bwd-dkv", "gqa-bwd-dq", "gqa-d64-fwd-lse",
         "gqa-d64-bwd-dkv", "gqa-d64-bwd-dq", "gqa-d64"],
)
def test_flash_attention_compiles_for_v5e(one_chip, shape, kernel):
    b, s, heads, kv_heads, head_dim, window = _FLASH_SHAPES[shape]
    blocks = FWD_BLOCKS if kernel.startswith("fwd") else BWD_BLOCKS
    spec = Spec(heads, kv_heads, head_dim, head_dim**-0.5, True, window,
                default_block(s, blocks[0]), default_block(s, blocks[1]))
    c = spec.kv_per_row
    q, kv = ((b, s, heads * head_dim), jnp.bfloat16), ((b, s, kv_heads * head_dim), jnp.bfloat16)
    rows = ((b, kv_heads // c, c * spec.group, s), jnp.float32)  # log-sum-exp, delta
    shapes = (q, kv, kv) if kernel.startswith("fwd") else (q, kv, kv, q, rows, rows)
    text = _compile_text(_flash_kernel(kernel, spec), one_chip, *shapes)
    assert f"flash_attention_{kernel.removesuffix('_lse')}" in text


def test_rg_lru_compiles_for_v5e(one_chip):
    # recurrentgemma-2b: lru_width 2560
    fn = functools.partial(rg_lru_scan_blocked, block_t=16, block_n=128, interpret=False)
    _compile_text(fn, one_chip, ((1, SEQ, 2560), jnp.float32), ((1, SEQ, 2560), jnp.float32))


def _ssd_shapes(batch, seq):
    # mamba2-370m: 32 heads x 64, d_state 128, one B/C group
    return (((batch, seq, 32, 64), jnp.float32), ((batch, seq, 32), jnp.float32),
            ((32,), jnp.float32), ((batch, seq, 1, 128), jnp.float32),
            ((batch, seq, 1, 128), jnp.float32))


def test_ssd_compiles_for_v5e(one_chip):
    def fwd(*args):
        return ssd_chunk_scan_fwd(*args, chunk=64, save_states=False, interpret=False)[:2]

    _compile_text(fwd, one_chip, *_ssd_shapes(1, SEQ))


def _ssd_fwd_states(*args):
    return ssd_chunk_scan_fwd(*args, chunk=64, save_states=True, interpret=False)


def test_ssd_forward_under_vjp_compiles_for_v5e(one_chip):
    # the mamba2-370m train cell: batch 8 x 2048
    _compile_text(_ssd_fwd_states, one_chip, *_ssd_shapes(8, 2048))


def test_ssd_backward_compiles_for_v5e(one_chip):
    shapes = _ssd_shapes(8, 2048)
    y, final, res = jax.eval_shape(
        _ssd_fwd_states, *(jax.ShapeDtypeStruct(s, d) for s, d in shapes))

    def bwd(*args):
        *res, dy, dfinal = args
        return ssd_chunk_scan_bwd(tuple(res), dy, dfinal, chunk=64, interpret=False)

    _compile_text(bwd, one_chip, *((v.shape, v.dtype) for v in (*res, y, final)))
