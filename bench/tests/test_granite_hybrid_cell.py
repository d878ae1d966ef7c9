"""The granite-4.0-h-micro-l10 cell's reference and check on the CPU at a
small size.

- The reference's blocked quadratic SSD against a token-by-token recurrence
  in float64: 1e-5 relative, float32 rounding of a 40-step sum of
  exponentials (blocks of 8 queries).
- A small stand-in of the cell (the cell's traffic at 1 x 64 tokens, its
  pattern cut to M A M) through ``harness/train.py``: a sound run is
  correct, and the control and the unchanged state are not. The half-batch
  fault needs two rows and the cell has one. The limits are for this size,
  set from CPU readings on seeds 1, 2, 3 and 123456789012: sound runs read
  grad_gap 4.1e-3 to 1.17e-2, grad_gap_median 6.7e-4 to 8.5e-4 and
  delta_gap 3.6e-3 to 4.5e-3; the control grad_gap 3.9e-2 to 8.7e-2 (6.5e-2
  on seed 2, the seed used here) and grad_gap_median 4.6e-3 to 1.07e-2; an
  unchanged state reads 1 on every gap. They are not the cell's own limits.
"""

import copy
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cell as cellmod
from harness import common, control, faults, train
from references import granite_hybrid_lm
from small import DEVICE

CONFIG = "granite-4.0-h-micro-l10"
TRAFFIC = "train-s16384"
M, A = ["ssd", "mlp"], ["attn", "mlp"]
SMALL = {
    "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "n_groups": 1,
    "pattern": [M, A, M], "vocab": 256, "attn_scale": 1 / 16,
    "ssm": {"d_inner": 128, "head_dim": 32, "d_state": 16, "n_groups": 1, "conv_width": 4},
}
LIMITS = {"grad_gap": 2e-2, "grad_gap_median": 3e-3, "delta_gap": 2e-2}
SEED = 2


def small_cell(seed: int = SEED) -> cellmod.Cell:
    """The cell's configuration with every size cut, under its own traffic at
    1 x 64 tokens."""
    config = common.config(CONFIG)
    model = dict(config["model"], **copy.deepcopy(SMALL))
    config.update(model=model, overrides=dict(model, ssm=dict(model["ssm"], chunk=16), attn_chunk_q=16))
    traffic = common.load_json(common.BENCH / "traffic" / f"{TRAFFIC}.json")
    traffic.update(global_batch=1, seq_len=64)
    name = f"{CONFIG}.{TRAFFIC}"
    w = {"name": name, "config": CONFIG, "traffic": TRAFFIC, "limits": dict(LIMITS)}
    return cellmod.Cell(name, w, config, traffic, 1, seed, 0.5, False, time.perf_counter())


def run(capsys, cell, hooks):
    train.run(cell, DEVICE, hooks=hooks)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_blocked_ssd_matches_recurrence():
    rng = np.random.default_rng(1)
    s, h, p, g, n = 40, 4, 8, 2, 6
    x = rng.standard_normal((s, h, p))
    dt = rng.uniform(0.01, 0.5, (s, h))
    a = -rng.uniform(0.5, 4.0, h)
    bm = rng.standard_normal((s, g, n))
    cm = rng.standard_normal((s, g, n))
    args = (jnp.asarray(v, jnp.float32) for v in (x, dt, a, bm, cm))
    with jax.default_matmul_precision("highest"):
        y = np.asarray(granite_hybrid_lm.ssd_blocked(*args, "f32", block=8))
    state = np.zeros((h, p, n))
    want = np.zeros((s, h, p))
    for t in range(s):
        for hh in range(h):
            gg = hh // (h // g)
            state[hh] = state[hh] * np.exp(dt[t, hh] * a[hh]) + dt[t, hh] * np.outer(x[t, hh], bm[t, gg])
            want[t, hh] = state[hh] @ cm[t, gg]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())


def test_sound_run_is_correct(capsys):
    out = run(capsys, small_cell(), {})
    assert out["correct"], out["checks"]
    want = {m["name"] for m in common.metrics_for(f"{CONFIG}.{TRAFFIC}", "end_to_end")}
    assert set(out["metrics"]) == want and "train_tokens_per_s" in want


def test_unchanged_state_is_not_correct(capsys):
    out = run(capsys, small_cell(), faults.TRAIN_FAULTS["unchanged_state"])
    assert not out["correct"], out["checks"]


def test_control_is_not_correct():
    checks = control.train_control(small_cell())
    assert any(v > lim for _, v, lim in checks), checks
