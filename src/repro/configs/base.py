"""Model / run configuration schema.

A model is a sequence of blocks described by *patterns*: ``head_pattern``
(unscanned prologue), ``pattern`` repeated ``n_groups`` times (stacked
params + ``jax.lax.scan`` — keeps HLO size and compile time flat in depth),
and ``tail_pattern`` (unscanned epilogue).

Block spec = (mixer, ffn):
  mixer: "attn" | "attn_local" | "mla" | "rglru" | "ssd" | "attn_bidir"
  ffn:   "mlp" | "moe" | "none"
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Optional, Tuple

BlockSpec = Tuple[str, str]


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: Optional[int] = None
    capacity_factor: float = 1.25
    group_size: int = 4096
    aux_weight: float = 0.001


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    d_inner: int
    head_dim: int = 64
    d_state: int = 128
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 64


@dataclass(frozen=True)
class RNNConfig:
    d_rnn: int
    conv_width: int = 4


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec models (whisper). The conv/mel frontend is a
    STUB: inputs are precomputed frame embeddings (B, source_len, d_model)."""

    n_layers: int
    source_len: int = 1500


@dataclass(frozen=True)
class FrontendConfig:
    """Modality frontend stub (VLM): precomputed patch embeddings are inputs."""

    kind: str  # "siglip_stub"
    n_tokens: int  # e.g. 256 patches
    dim: int  # embedding dim delivered by the stub (== d_model after proj)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | vlm | audio
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    # layer structure
    pattern: Tuple[BlockSpec, ...]
    n_groups: int
    head_pattern: Tuple[BlockSpec, ...] = ()
    tail_pattern: Tuple[BlockSpec, ...] = ()
    head_dim: Optional[int] = None  # default d_model // n_heads
    # attention details
    rope_theta: float = 10_000.0
    rope_theta_local: Optional[float] = None  # local layers (gemma3: 10k vs 1M global)
    window: Optional[int] = None
    qkv_bias: bool = False
    qk_norm: bool = False
    logit_softcap: Optional[float] = None
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma: embeddings * sqrt(d_model)
    # Granite-style (muP) multipliers; 1.0 / None leave the model as it was.
    embed_multiplier: float = 1.0  # embeddings * this
    residual_multiplier: float = 1.0  # each sublayer's output * this before the residual add
    logits_divisor: float = 1.0  # logits / this
    attn_scale: Optional[float] = None  # softmax scale; None = 1 / sqrt(head_dim)
    activation: str = "silu"
    norm_type: str = "rms"  # rms | layer (whisper)
    gated_mlp: bool = True  # False: plain w1/gelu/w2 (whisper)
    pos_embed: str = "rope"  # rope | learned (whisper) | none (NoPE: no rotary in attention)
    max_pos: int = 32_768  # learned-position table size
    norm_eps: float = 1e-6
    # sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rnn: Optional[RNNConfig] = None
    encoder: Optional[EncoderConfig] = None
    frontend: Optional[FrontendConfig] = None
    # training / lowering knobs
    remat: str = "full"  # none | full | dots
    # mixed precision: cast >=2D fp32 params to bf16 once per step before the
    # stack — halves FSDP all-gather wire bytes and gathered-weight buffers;
    # fp32 master weights live in the optimizer update (standard recipe).
    params_compute_dtype: str = "float32"  # float32 | bfloat16
    use_flash_kernel: bool = False  # Pallas path (TPU target; interpret in tests)
    use_scan_kernels: bool = False  # Pallas rg_lru / ssd kernels
    attn_chunk_q: int = 512  # query-chunked attention; 0 = naive S^2 (baseline)
    chunked_loss_chunks: int = 8  # 0/1 = materialize full logits (baseline path)
    # Megatron-SP: residual-stream sharding (batch_axes, seq_axes) applied as
    # with_sharding_constraint at block boundaries.  Set by the dist layer;
    # None on CPU/smoke paths (no mesh context).
    act_pspec: Optional[Tuple[Any, Any]] = None

    # -- derived -------------------------------------------------------------

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def layer_specs(self) -> Tuple[BlockSpec, ...]:
        return self.head_pattern + self.pattern * self.n_groups + self.tail_pattern

    @property
    def n_layers(self) -> int:
        n = len(self.layer_specs)
        if self.encoder is not None:
            n += self.encoder.n_layers
        return n

    def scaled(self, **overrides) -> "ModelConfig":
        return replace(self, **overrides)

