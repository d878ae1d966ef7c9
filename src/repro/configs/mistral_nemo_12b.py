"""mistral-nemo-12b — dense GQA, 128k ctx [hf:mistralai/Mistral-Nemo-Base-2407].

40L d_model=5120 32H (GQA kv=8) head_dim=128 d_ff=14336 vocab=131072.
"""

from .base import ModelConfig

CONFIG = ModelConfig(
    name="mistral-nemo-12b",
    family="dense",
    d_model=5120,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    vocab=131072,
    pattern=(("attn", "mlp"),),
    n_groups=40,
    rope_theta=1_000_000.0,
    use_flash_kernel=True,  # train attention through the Pallas flash kernel pair
)

SMOKE = ModelConfig(
    name="mistral-nemo-12b-smoke",
    family="dense",
    d_model=128,
    n_heads=8,
    n_kv_heads=2,
    head_dim=16,
    d_ff=256,
    vocab=512,
    pattern=(("attn", "mlp"),),
    n_groups=2,
    rope_theta=1_000_000.0,
    remat="none",
)
