"""Model FLOPs from a configuration's shapes, one counter per layer kind.

A multiply-add is 2 FLOPs. Training counts the forward and backward passes
as 3x the forward; recomputation under remat is not counted. Causal
attention and the SSD intra-chunk term count the lower triangle only, so
a utilization built on these counts is never above what the chip did.
"""

from __future__ import annotations

from typing import Any, Dict

Model = Dict[str, Any]


def ssd_matmul_params(m: Model) -> int:
    s = m["ssm"]
    d, di, g, n = m["d_model"], s["d_inner"], s["n_groups"], s["d_state"]
    h = di // s["head_dim"]
    return d * (2 * di + 2 * g * n + h) + di * d


def ssd_mixer_fwd(m: Model, seq_len: int) -> float:
    """Per-token forward FLOPs of the chunked SSD beyond its projections."""
    s = m["ssm"]
    q = min(s["chunk"], seq_len)
    h, p, n, g = s["d_inner"] // s["head_dim"], s["head_dim"], s["d_state"], s["n_groups"]
    scores = g * q * n  # C_i . B_j over the causal half of a chunk: 2 * q/2 * n
    y_diag = h * q * p  # scores @ (dt x): 2 * q/2 * p per head
    states = 2 * h * p * n  # chunk state from B and x
    y_off = 2 * h * p * n  # C . carried state
    return float(scores + y_diag + states + y_off)


def attn_matmul_params(m: Model) -> int:
    d, hd = m["d_model"], m["head_dim"]
    return d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2


def attn_mixer_fwd(m: Model, context: float) -> float:
    """Per-token forward FLOPs of scores and weighted values at an average
    of ``context`` keys per query."""
    return float(2 * 2 * context * m["n_heads"] * m["head_dim"])


def mlp_matmul_params(m: Model) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def head_matmul_params(m: Model) -> int:
    return m["d_model"] * m["vocab_padded"]


def layers(m: Model):
    return [tuple(spec) for spec in m["pattern"]] * m["n_groups"]


def matmul_params(m: Model) -> int:
    total = head_matmul_params(m)
    for mixer, ffn in layers(m):
        if mixer == "ssd":
            total += ssd_matmul_params(m)
        elif mixer == "attn":
            total += attn_matmul_params(m)
        else:
            raise ValueError(f"no FLOP counter for mixer {mixer!r}")
        if ffn == "mlp":
            total += mlp_matmul_params(m)
        elif ffn != "none":
            raise ValueError(f"no FLOP counter for ffn {ffn!r}")
    return total


def forward_flops_per_token(m: Model, seq_len: int, context: float) -> float:
    """``context``: keys an attention query sees on average ((S+1)/2 for
    causal training over S tokens, the cache length in decode)."""
    total = 2.0 * matmul_params(m)
    for mixer, _ in layers(m):
        if mixer == "ssd":
            total += ssd_mixer_fwd(m, seq_len)
        elif mixer == "attn":
            total += attn_mixer_fwd(m, context)
    return total


def train_flops_per_token(m: Model, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq_len, (seq_len + 1) / 2.0)
