"""granite-4.0-h-micro's hybrid stack against the benchmark's plain float32
reference (``bench/references/granite_hybrid_lm.py``), at the smoke size on
the CPU with the benchmark's seeded weights.

Tolerances, and why (readings on this CPU in brackets):
- logits: max |program - reference| <= 5% of max |logit| [1.4%]. The
  program rounds its residual stream and every matmul input to bfloat16
  (relative 2^-8 a rounding), about ten roundings a layer; the fp8 control
  (e4m3, 3 mantissa bits) must be off by more than 8% [13.9%], and so must
  the reference with any one multiplier set to 1 [21% to 700%].
- first-step loss: |program - reference| <= 2e-4 [1.9e-5]: a mean of 128
  cross entropies near ln(512), whose bfloat16 errors mostly cancel.
- gradient norm per leaf: |program - reference| / max(reference, median
  leaf) <= 2e-2 for the worst leaf [4.1e-3, a per-head D skip] and 5e-3 for
  the median leaf [5.3e-4], the cell's measure at this size.
- prefill then decode: the logits' 5% [1.3%], the same roundings.
- NoPE attention alone, in float32 on both sides: 1e-4 of max |output|
  [9.6e-8; 2.8e-7 through the cache], float32 rounding of a 32-key
  softmax; the reference with rotary on must be off by more than 10% [78%].
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))

from harness import common  # noqa: E402
from harness.weights import leaf_names, make_init, norms  # noqa: E402
from references import granite_hybrid_lm as ref  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.dist.train import abstract_state  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import cache_init, decode_step, lm_apply, lm_loss, prefill  # noqa: E402
from repro.models.layers import lm_logits  # noqa: E402
from repro.models.lm import _head_matrix  # noqa: E402

ARCH = "granite-4.0-h-micro"
B, S = 2, 64


def model_of(cfg):
    """The reference's view of a program configuration."""
    s = cfg.ssm
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "n_groups": cfg.n_groups, "pattern": [list(p) for p in cfg.pattern],
            "tie_embeddings": cfg.tie_embeddings, "norm_eps": cfg.norm_eps,
            "pos_embed": cfg.pos_embed, "rope_theta": cfg.rope_theta, "attn_scale": cfg.attn_scale,
            "embed_multiplier": cfg.embed_multiplier,
            "residual_multiplier": cfg.residual_multiplier, "logits_divisor": cfg.logits_divisor,
            "ssm": {"d_inner": s.d_inner, "head_dim": s.head_dim, "d_state": s.d_state,
                    "n_groups": s.n_groups}}


@pytest.fixture(scope="module")
def case():
    cfg = get_smoke_config(ARCH)
    params = jax.jit(make_init(abstract_state(cfg)[0]))(common.seed_key(5))
    tokens = np.random.default_rng(0).integers(2, cfg.vocab, (B, S + 1)).astype(np.int32)
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


def ref_logits(model, params, tokens, mode="f32"):
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, t: ref.logits(model, p, t, mode))
        return np.stack([np.asarray(fn(params, row)) for row in tokens])


def rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def program_logits(case):
    cfg, params, tokens, _ = case
    h, _ = jax.jit(lambda p, t: lm_apply(cfg, p, t))(params, tokens)
    return np.asarray(lm_logits(h, _head_matrix(cfg, params), None, cfg.logits_divisor))


def test_config_is_the_published_period():
    cfg = get_config(ARCH)
    assert [m for m, _ in cfg.pattern] == ["ssd"] * 5 + ["attn"] + ["ssd"] * 4
    assert all(f == "mlp" for _, f in cfg.pattern) and cfg.n_groups == 4
    assert (cfg.pos_embed, cfg.attn_scale, cfg.embed_multiplier, cfg.residual_multiplier,
            cfg.logits_divisor) == ("none", 1 / 64, 12.0, 0.22, 8.0)


def test_logits_match_reference_and_fp8_control_does_not(case, program_logits):
    cfg, params, tokens, _ = case
    model = model_of(cfg)
    assert rel(program_logits, ref_logits(model, params, tokens)) <= 0.05
    assert rel(program_logits, ref_logits(model, params, tokens, "fp8")) > 0.08


@pytest.mark.parametrize("key,value", [
    ("residual_multiplier", 1.0),
    ("embed_multiplier", 1.0),
    ("logits_divisor", 1.0),
    ("attn_scale", 1 / np.sqrt(32)),
])
def test_reference_without_a_multiplier_fails(case, program_logits, key, value):
    cfg, params, tokens, _ = case
    assert rel(program_logits, ref_logits(dict(model_of(cfg), **{key: value}), params, tokens)) > 0.05


@pytest.mark.parametrize("use_scan_kernels", [False, True])
def test_loss_and_gradients_match_reference(case, use_scan_kernels):
    """The train step's loss and gradient under per-block remat, with the
    SSD scan in XLA and through the Pallas pair (interpreted here)."""
    cfg, params, tokens, labels = case
    cfg = cfg.scaled(remat="full", use_scan_kernels=use_scan_kernels)
    batch = {"tokens": tokens, "labels": labels}
    (loss, _), grads = jax.jit(jax.value_and_grad(lambda p: lm_loss(cfg, p, batch), has_aux=True))(params)
    model = model_of(cfg)

    def ref_loss(p):
        return sum(ref.row_loss(model, p, tokens[r], labels[r], "f32") for r in range(B)) / tokens.size

    with jax.default_matmul_precision("highest"):
        want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(params)
    assert abs(float(loss) - float(want_loss)) <= 2e-4
    got, want = np.asarray(norms(grads, cfg.n_groups)[0]), np.asarray(norms(want_grads, cfg.n_groups)[0])
    gap = np.abs(got - want) / np.maximum(want, np.median(want))
    worst = leaf_names(params)[int(np.argmax(gap))]
    assert gap.max() <= 2e-2, (worst, gap.max())
    assert np.median(gap) <= 5e-3


def test_prefill_then_decode_match_reference(case):
    """Prefill half the row through the hybrid cache (SSD state beside a NoPE
    KV cache), decode the rest a token at a time; every step's logits
    against the reference's full forward pass."""
    cfg, params, tokens, _ = case
    want = ref_logits(model_of(cfg), params, tokens)
    n_pre = S // 2
    logits, cache = jax.jit(lambda p, t: prefill(cfg, p, t, S))(params, tokens[:, :n_pre])
    got = [np.asarray(logits)[:, 0]]
    step = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
    for t in range(n_pre, S):
        logits, cache = step(params, cache, tokens[:, t : t + 1])
        got.append(np.asarray(logits)[:, 0])
    assert rel(np.stack(got, axis=1), want[:, n_pre - 1 :]) <= 0.05
    # from an empty cache too: decode alone covers every position
    cache = cache_init(cfg, params, B, S)
    for t in range(8):
        logits, cache = step(params, cache, tokens[:, t : t + 1])
    assert rel(np.asarray(logits)[:, 0], want[:, 7]) <= 0.05


def test_nope_attention_matches_reference_and_rotary_does_not():
    """One NoPE attention layer in float32 on both sides, at a softmax scale
    of 1/sqrt(d) so that position would show; the cached path (prefill then
    decode) rotates nothing either."""
    d_model, h, k, d, s = 64, 4, 2, 16, 32
    p = attn.gqa_init(jax.random.PRNGKey(0), d_model, h, k, d)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, s, d_model), jnp.float32)
    scale = 1 / np.sqrt(d)
    kw = dict(n_heads=h, n_kv_heads=k, head_dim=d, rope_theta=None)
    got = np.asarray(attn.gqa_apply(p, u, scale=scale, **kw))[0]
    model = {"n_heads": h, "n_kv_heads": k, "head_dim": d, "attn_scale": scale,
             "pos_embed": "none", "rope_theta": 10000.0}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._attention(model, p, u[0], "f32"))
        rotary = np.asarray(ref._attention(dict(model, pos_embed="rope"), p, u[0], "f32"))
    assert rel(got, want) <= 1e-4
    assert rel(got, rotary) > 0.1

    kw.pop("rope_theta")
    cache = attn.gqa_prefill_cache(p, u[:, : s // 2], s, rope_theta=None, **kw)
    outs = []
    for t in range(s // 2, s):
        out, cache = attn.gqa_decode(p, u[:, t : t + 1], cache, jnp.int32(t), rope_theta=None,
                                     scale=scale, **kw)
        outs.append(np.asarray(out)[0, 0])
    assert rel(np.stack(outs), want[s // 2 :]) <= 1e-4
