"""mamba2-370m — attention-free SSM with SSD [arXiv:2405.21060; unverified].

48L d_model=1024 (attn-free) vocab=50280, d_inner=2048 (expand 2),
head_dim=64 (32 heads), d_state=128, SSD chunked scan.
"""

from .base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="mamba2-370m",
    family="ssm",
    d_model=1024,
    n_heads=1,  # attention-free; placeholder
    n_kv_heads=1,
    head_dim=64,
    d_ff=0,
    vocab=50280,
    pattern=(("ssd", "none"),),
    n_groups=48,
    ssm=SSMConfig(d_inner=2048, head_dim=64, d_state=128, n_groups=1, conv_width=4, chunk=64),
    tie_embeddings=True,
    sub_quadratic=True,  # O(1) SSM state
    use_scan_kernels=True,  # train the SSD scan through the Pallas kernel pair
)

SMOKE = ModelConfig(
    name="mamba2-370m-smoke",
    family="ssm",
    d_model=128,
    n_heads=1,
    n_kv_heads=1,
    head_dim=32,
    d_ff=0,
    vocab=512,
    pattern=(("ssd", "none"),),
    n_groups=2,
    ssm=SSMConfig(d_inner=256, head_dim=32, d_state=16, n_groups=1, conv_width=4, chunk=8),
    tie_embeddings=True,
    sub_quadratic=True,
    remat="none",
)
