"""The train cells' check, driven end to end on the CPU at small sizes:
a sound run is correct, and the control and each fault a one-chip train
cell can have come out not correct.

The limits here are for these small sizes (4 x 64 tokens), set from CPU
readings on seeds 1, 2, 3 and 123456789012: sound runs read loss_gap (first
step) 7.8e-6 to 2.7e-4, grad_gap 1.1e-3 to 6.4e-3, delta_gap 9.7e-4 to
6.2e-3; the control grad_gap 8.2e-3 to 5.9e-2 (2.8e-2 on seed 2, the seed
used here); half the batch grad_gap 4.1e-2 to 0.23 and delta_gap 4.65e-2
to 0.165. They are not the cells' own limits."""

import json

import pytest

from harness import control, faults, train
from harness import common
from small import DEVICE, small_cell

CELLS = [("mamba2-370m", "train-b8s2048"), ("mistral-nemo-12b-l2", "train-s4096")]
SMALL = {"global_batch": 4, "seq_len": 64}
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "delta_gap": 2e-2}
SEED = 2


def run(capsys, cell, hooks):
    train.run(cell, DEVICE, hooks=hooks)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(capsys, workload):
    out = run(capsys, small_cell(*workload, seed=SEED, traffic_overrides=SMALL, limits=LIMITS), {})
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in common.metrics_for(".".join(workload), "end_to_end")}
    assert set(out["metrics"]) == want and "train_tokens_per_s" in want


@pytest.mark.parametrize("fault", sorted(faults.TRAIN_FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(capsys, workload, fault):
    out = run(capsys, small_cell(*workload, seed=SEED, traffic_overrides=SMALL, limits=LIMITS),
              faults.TRAIN_FAULTS[fault])
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    checks = control.train_control(small_cell(*workload, seed=SEED, traffic_overrides=SMALL, limits=LIMITS))
    assert any(v > lim for _, v, lim in checks), checks
