"""FLOP counts of bench/harness/flops.py against counts by hand, at the
small sizes of tests/small.py at the program's blocking, and the peak table's lookup."""

import pytest

from harness import common, flops
from small import small_cell


def counted(config: str, traffic: str):
    """The small cell's shapes at the blocking the program runs them."""
    cell = small_cell(config, traffic)
    return cell.counted_model(cell.program_config())


def test_mamba2_small_by_hand():
    m = counted("mamba2-370m", "train-b8s2048")
    # per layer: in_proj 64 x (2*128 + 2*16 + 4 heads), out_proj 128 x 64
    per_layer = 64 * (2 * 128 + 2 * 16 + 4) + 128 * 64
    head = 64 * 256  # tied head over the padded vocabulary
    assert flops.matmul_params(m) == 2 * per_layer + head == 70144
    # SSD per token and layer, chunk 16, 4 heads of 32, state 16, 1 group:
    # C.B 16*16, scores@x 4*16*32, chunk states 2*4*32*16, C.state 2*4*32*16
    ssd = 16 * 16 + 4 * 16 * 32 + 2 * 4 * 32 * 16 + 2 * 4 * 32 * 16
    assert flops.ssd_mixer_fwd(m, 64) == ssd == 10496
    assert flops.train_flops_per_token(m, 64) == 3 * (2 * 70144 + 2 * ssd) == 483840


def test_mistral_small_by_hand():
    m = counted("mistral-nemo-12b-l2", "train-s4096")
    attn = 64 * 4 * 16 * 2 + 64 * 2 * 16 * 2  # wq, wo; wk, wv
    mlp = 3 * 64 * 128
    head = 64 * 256
    assert flops.matmul_params(m) == 2 * (attn + mlp) + head == 90112
    # causal over 64 tokens: 32.5 keys per query on average; QK and AV
    per_layer_attn = 2 * 2 * 32.5 * 4 * 16
    assert flops.train_flops_per_token(m, 64) == 3 * (2 * 90112 + 2 * per_layer_attn) == 590592


def test_full_configs_match_their_published_sizes():
    mamba = dict(common.config("mamba2-370m")["model"], vocab_padded=50432)
    assert flops.matmul_params(mamba) == 48 * (1024 * 4384 + 2048 * 1024) + 1024 * 50432
    nemo = dict(common.config("mistral-nemo-12b-l2")["model"], vocab_padded=16384)
    assert flops.matmul_params(nemo) == 2 * (5120 * 4096 * 2 + 5120 * 1024 * 2 + 3 * 5120 * 14336) + 5120 * 16384


def test_unknown_device_kind_is_an_error():
    assert common.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(common.BenchError):
        common.peaks("cpu")
