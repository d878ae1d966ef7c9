"""Per-architecture smoke tests (assignment requirement).

For each of the 10 assigned archs: instantiate the REDUCED config of the
same family, run one forward/train step on CPU, assert output shapes and
no NaNs.  Also checks decode-vs-prefill logit consistency (exact for
deterministic mixers; no-drop capacity for MoE).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import cache_init, decode_step, lm_init, lm_loss, prefill
from repro.models.lm import padded_vocab

B, S = 2, 16


def _batch(cfg, key):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if cfg.frontend is not None:
        batch["patches"] = jax.random.normal(
            key, (B, cfg.frontend.n_tokens, cfg.frontend.dim), jnp.bfloat16
        )
    if cfg.encoder is not None:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_exactness(arch):
    """The FULL config matches the assignment spec (exercised via dry-run only)."""
    cfg = get_config(arch)
    spec = {
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "yi-34b": (60, 7168, 56, 8, 20480, 64000),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "mamba2-370m": (48, 1024, 1, 1, 0, 50280),
        "whisper-large-v3": (64, 1280, 20, 20, 5120, 51866),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }[arch]
    n_layers, d_model, n_heads, n_kv, d_ff, vocab = spec
    assert cfg.n_layers == n_layers
    assert cfg.d_model == d_model
    assert cfg.n_heads == n_heads
    assert cfg.n_kv_heads == n_kv
    assert cfg.vocab == vocab
    if cfg.moe is not None:
        assert cfg.moe.d_ff_expert == d_ff
        assert (cfg.moe.n_experts, cfg.moe.top_k) == {
            "deepseek-moe-16b": (64, 6),
            "deepseek-v2-236b": (160, 6),
        }[arch]
        assert cfg.moe.n_shared == 2
    elif arch == "mamba2-370m":
        assert cfg.ssm is not None and cfg.ssm.d_state == 128
    else:
        assert cfg.d_ff == d_ff
    if arch == "granite-4.0-h-micro":
        kinds = [mixer for mixer, _ in cfg.layer_specs]
        assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
        assert (cfg.ssm.d_inner, cfg.ssm.head_dim, cfg.ssm.d_state) == (4096, 64, 128)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = lm_init(key, cfg)
    batch = _batch(cfg, key)

    def loss_fn(p):
        loss, metrics = lm_loss(cfg, p, batch)
        return loss

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss))
    # expected initial loss ~ ln(padded_vocab) for random init
    assert abs(float(loss) - np.log(padded_vocab(cfg.vocab))) < 1.5
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g, dtype=np.float32))) for g in flat)
    # at least one nonzero gradient leaf
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in flat)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode_consistency(arch):
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        # no-drop capacity so prefill (tokens compete for expert slots) and
        # single-token decode route identically
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.n_experts))
        )
    key = jax.random.PRNGKey(1)
    params = lm_init(key, cfg)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    kw = {}
    if cfg.frontend is not None:
        kw["patches"] = jax.random.normal(key, (B, cfg.frontend.n_tokens, cfg.frontend.dim), jnp.bfloat16)
    if cfg.encoder is not None:
        kw["frames"] = jax.random.normal(key, (B, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16)
    max_len = 32

    logits_prefill, cache = prefill(cfg, params, toks, max_len, **kw)
    assert logits_prefill.shape == (B, 1, padded_vocab(cfg.vocab))
    assert np.all(np.isfinite(np.asarray(logits_prefill)))
    assert int(cache["index"]) == S + (cfg.frontend.n_tokens if cfg.frontend else 0)

    if cfg.frontend is not None:
        # VLM: image prefix enters via prefill; check one decode step works
        logits_d, cache = decode_step(cfg, params, cache, toks[:, -1:])
        assert np.all(np.isfinite(np.asarray(logits_d)))
        return

    c = cache_init(cfg, params, B, max_len, frames=kw.get("frames"))
    logits_d = None
    for t in range(S):
        logits_d, c = decode_step(cfg, params, c, toks[:, t : t + 1])
    np.testing.assert_allclose(
        np.asarray(logits_d, np.float32),
        np.asarray(logits_prefill, np.float32),
        rtol=2e-2,
        atol=2e-2,
    )


@pytest.mark.parametrize("arch", ["gemma3-12b", "recurrentgemma-2b"])
def test_window_cache_bounded(arch):
    """Local-attention caches must be ring buffers of window size — this is
    what makes long_500k feasible for the sub-quadratic archs."""
    cfg = get_smoke_config(arch)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    max_len = 64
    c = cache_init(cfg, params, B, max_len)

    def find_local_caches(tree):
        out = []
        if isinstance(tree, dict):
            if "k" in tree and "v" in tree:
                out.append(tree)
            else:
                for v in tree.values():
                    out.extend(find_local_caches(v))
        elif isinstance(tree, list):
            for v in tree:
                out.extend(find_local_caches(v))
        return out

    kvs = find_local_caches(c)
    assert kvs
    sizes = sorted({kv["k"].shape[-3] for kv in kvs})
    assert cfg.window in sizes  # at least the local layers are window-bounded
    for size in sizes:
        assert size <= max_len


def test_long_decode_past_window():
    """Decode far past the window: ring buffer + RG-LRU state stay finite and
    depend on position (sanity for long_500k semantics)."""
    cfg = get_smoke_config("recurrentgemma-2b")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    c = cache_init(cfg, params, 1, cfg.window)  # max_len == window
    step = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
    logits = None
    for t in range(cfg.window * 3):
        logits, c = step(params, c, jnp.full((1, 1), t % cfg.vocab, jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits)))
    assert int(c["index"]) == cfg.window * 3
