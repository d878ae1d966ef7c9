"""The control of a train cell: the plain reference computed with fp8
matmuls (one step below the program's bfloat16) put in the program's place,
read by the same checks against the float32 reference.

    python bench/check_readings.py --workload <train cell> --control --seeds 1 2 3
"""

from __future__ import annotations

from typing import List

from . import cell as cellmod
from . import common, traffic
from .train import common_reference, train_checks
from .weights import leaf_names, make_init


def train_control(cell: cellmod.Cell) -> List[cellmod.Check]:
    from repro.dist.train import abstract_state

    t = cell.traffic
    cfg = cell.program_config()
    shapes = abstract_state(cfg)[0]
    init = make_init(shapes)
    key = common.seed_key(cell.seed)
    batches = [traffic.train_batch(t, cell.model["vocab"], cell.seed, j)
               for j in range(t["checked_steps"])]
    ref = cell.reference()
    want = common_reference(ref, cell.model, init, key, batches, t["optimizer"], "f32")
    got = common_reference(ref, cell.model, init, key, batches, t["optimizer"], "fp8")
    return train_checks(got, want, cell.workload["limits"], leaf_names(shapes))
