"""whisper-large-v3 — encoder-decoder ASR backbone [arXiv:2212.04356;
unverified].

32+32L d_model=1280 20H (MHA kv=20) head_dim=64 d_ff=5120 vocab=51866.
Conv/mel frontend is a STUB per the assignment: ``input_specs`` supplies
1500 precomputed frame embeddings.  LayerNorm, plain GELU MLP, learned
positions, QKV bias — whisper's actual block recipe.

Note: the learned-position table holds 32k positions, so the decoder runs
mechanically beyond whisper's trained 448-token horizon.
"""

from .base import EncoderConfig, ModelConfig

CONFIG = ModelConfig(
    name="whisper-large-v3",
    family="audio",
    d_model=1280,
    n_heads=20,
    n_kv_heads=20,
    head_dim=64,
    d_ff=5120,
    vocab=51866,
    pattern=(("attn", "mlp"),),
    n_groups=32,
    qkv_bias=True,
    norm_type="layer",
    gated_mlp=False,
    pos_embed="learned",
    max_pos=32_768,
    activation="gelu",
    encoder=EncoderConfig(n_layers=32, source_len=1500),
)

SMOKE = ModelConfig(
    name="whisper-large-v3-smoke",
    family="audio",
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    head_dim=32,
    d_ff=256,
    vocab=512,
    pattern=(("attn", "mlp"),),
    n_groups=2,
    qkv_bias=True,
    norm_type="layer",
    gated_mlp=False,
    pos_embed="learned",
    max_pos=128,
    activation="gelu",
    encoder=EncoderConfig(n_layers=2, source_len=16),
    remat="none",
)
