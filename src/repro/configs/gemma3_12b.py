"""gemma3-12b — dense, 5:1 local:global interleave, 128k ctx
[hf:google/gemma-3-1b-pt; unverified].

48L d_model=3840 16H (GQA kv=8) head_dim=256 d_ff=15360 vocab=262144.
Local layers: window 1024, rope theta 10k; global layers: rope theta 1M.
QK-norm, tied embeddings, embeddings scaled by sqrt(d).
"""

from .base import ModelConfig

_PATTERN = (("attn_local", "mlp"),) * 5 + (("attn", "mlp"),)

CONFIG = ModelConfig(
    name="gemma3-12b",
    family="dense",
    d_model=3840,
    n_heads=16,
    n_kv_heads=8,
    head_dim=256,
    d_ff=15360,
    vocab=262144,
    pattern=_PATTERN,
    n_groups=8,
    window=1024,
    rope_theta=1_000_000.0,
    rope_theta_local=10_000.0,
    qk_norm=True,
    tie_embeddings=True,
    embed_scale=True,
    activation="gelu",
)

SMOKE = ModelConfig(
    name="gemma3-12b-smoke",
    family="dense",
    d_model=128,
    n_heads=4,
    n_kv_heads=2,
    head_dim=32,
    d_ff=256,
    vocab=512,
    pattern=_PATTERN,
    n_groups=2,
    window=8,
    rope_theta=1_000_000.0,
    rope_theta_local=10_000.0,
    qk_norm=True,
    tie_embeddings=True,
    embed_scale=True,
    activation="gelu",
    remat="none",
)
