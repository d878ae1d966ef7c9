"""Small configurations and cells for the CPU tests: the shapes of the
benchmark's configurations with every size cut, and the cells' traffic
and monitor as they are."""

from __future__ import annotations

import copy
import time

from harness import cell as cellmod
from harness import common

SMALL_MODELS = {
    "mamba2-370m": {
        "arch": "mamba2-370m", "reference": "ssm_lm",
        "model": {"d_model": 64, "n_groups": 2, "pattern": [["ssd", "none"]], "vocab": 250,
                  "tie_embeddings": True, "norm_eps": 1e-5,
                  "ssm": {"d_inner": 128, "head_dim": 32, "d_state": 16, "n_groups": 1,
                          "conv_width": 4}},
        "blocking": {"ssm": {"chunk": 16}},
    },
    "mistral-nemo-12b-l2": {
        "arch": "mistral-nemo-12b", "reference": "gqa_lm",
        "model": {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "n_groups": 2, "pattern": [["attn", "mlp"]], "vocab": 256,
                  "tie_embeddings": False, "rope_theta": 1e6, "norm_eps": 1e-5},
        "blocking": {"attn_chunk_q": 16},
    },
}

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def small_cell(config: str, traffic_name: str, seed: int = 123456789012, seconds: float = 0.5,
               trace=False, traffic_overrides=None, limits=None) -> cellmod.Cell:
    """A cell of ``config``'s small stand-in under the traffic file
    ``traffic_name``, with ``limits`` for its check."""
    small = copy.deepcopy(SMALL_MODELS[config])
    overrides = dict(small["model"])
    for key, value in small.pop("blocking").items():
        overrides[key] = dict(overrides[key], **value) if isinstance(value, dict) else value
    cfg = dict(small, name=config, overrides=overrides)
    traffic = common.load_json(common.BENCH / "traffic" / f"{traffic_name}.json")
    traffic.update(traffic_overrides or {})
    name = f"{config}.{traffic_name}"  # the cell's name: its metrics are read
    w = {"name": name, "config": config, "traffic": traffic_name, "limits": limits or {}}
    return cellmod.Cell(name, w, cfg, traffic, 1, seed, seconds, trace, time.perf_counter())
