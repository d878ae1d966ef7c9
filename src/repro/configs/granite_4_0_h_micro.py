"""granite-4.0-h-micro — hybrid Mamba-2 / NoPE GQA, dense SwiGLU after every
mixer [hf:ibm-granite/granite-4.0-h-micro, model_type granitemoehybrid].

40L d_model=2048 vocab=100352 (tied). ``layer_types`` puts attention at
layers 5, 15, 25, 35 and Mamba-2 elsewhere: a period of 10 layers,
M M M M M A M M M M. Mamba-2: 64 heads x 64 (d_inner 4096), d_state 128,
1 group, conv 4 (published chunk 256; the program scans in chunks of 64).
Attention: 32 query / 8 KV heads x 64, no position embedding, softmax
scale 1/64. Every layer has a SwiGLU MLP of 8192. Multipliers:
embedding x 12, each sublayer's output x 0.22 before the residual add,
logits / 8. rms eps 1e-5. No experts (``num_local_experts`` 0).
"""

from .base import ModelConfig, SSMConfig

_M, _A = ("ssd", "mlp"), ("attn", "mlp")
PERIOD = (_M,) * 5 + (_A,) + (_M,) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    pattern=PERIOD,
    n_groups=4,
    ssm=SSMConfig(d_inner=4096, head_dim=64, d_state=128, n_groups=1, conv_width=4, chunk=64),
    tie_embeddings=True,
    pos_embed="none",
    attn_scale=0.015625,
    embed_multiplier=12.0,
    residual_multiplier=0.22,
    logits_divisor=8.0,
    norm_eps=1e-5,
    use_scan_kernels=True,  # train the SSD scan through the Pallas kernel pair
    use_flash_kernel=True,  # and attention through the flash kernel pair
)

SMOKE = ModelConfig(
    name="granite-4.0-h-micro-smoke",
    family="hybrid",
    d_model=128,
    n_heads=4,
    n_kv_heads=2,
    head_dim=32,
    d_ff=256,
    vocab=512,
    pattern=(_M, _A, _M),
    n_groups=2,
    ssm=SSMConfig(d_inner=256, head_dim=32, d_state=16, n_groups=1, conv_width=4, chunk=8),
    tie_embeddings=True,
    pos_embed="none",
    attn_scale=1.0 / 32,
    embed_multiplier=12.0,
    residual_multiplier=0.22,
    logits_divisor=8.0,
    norm_eps=1e-5,
    remat="none",
)
