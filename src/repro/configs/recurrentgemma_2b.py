"""recurrentgemma-2b — Griffin hybrid: RG-LRU + local attention, 1:2
[arXiv:2402.19427; hf].

26L d_model=2560 10H (MQA kv=1) head_dim=256 d_ff=7680 vocab=256000,
lru_width=2560, window=2048.  Layout: (recurrent, recurrent, attention)
repeated; 26 = 8 x (R,R,A) + (R,R).
"""

from .base import ModelConfig, RNNConfig

CONFIG = ModelConfig(
    name="recurrentgemma-2b",
    family="hybrid",
    d_model=2560,
    n_heads=10,
    n_kv_heads=1,
    head_dim=256,
    d_ff=7680,
    vocab=256000,
    pattern=(("rglru", "mlp"), ("rglru", "mlp"), ("attn_local", "mlp")),
    n_groups=8,
    tail_pattern=(("rglru", "mlp"), ("rglru", "mlp")),
    window=2048,
    rope_theta=10_000.0,
    rnn=RNNConfig(d_rnn=2560, conv_width=4),
    tie_embeddings=True,
    embed_scale=True,
    activation="gelu",
)

SMOKE = ModelConfig(
    name="recurrentgemma-2b-smoke",
    family="hybrid",
    d_model=128,
    n_heads=4,
    n_kv_heads=1,
    head_dim=32,
    d_ff=256,
    vocab=512,
    pattern=(("rglru", "mlp"), ("rglru", "mlp"), ("attn_local", "mlp")),
    n_groups=2,
    tail_pattern=(("rglru", "mlp"), ("rglru", "mlp")),
    window=8,
    rnn=RNNConfig(d_rnn=128, conv_width=4),
    tie_embeddings=True,
    embed_scale=True,
    activation="gelu",
    remat="none",
)
