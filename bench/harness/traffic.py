"""The one traffic generator: every mix is a set of parameters in a cell's
workload file, and the same seed gives the same tokens.

Token ids are drawn from [2, vocab) with the mass skewed to low ids (the
square of a uniform, as a stand-in for a unigram law), with a beginning-of-
document id 1 at a rate of ``bos_every``. Batches are keyed by
(seed, stream, index), so every step has rows of its own
and a batch never depends on how many came before it.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

TRAIN_STREAM = 1


def _rng(seed: int, stream: int, index: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream, index])


def tokens(rng: np.random.Generator, shape, vocab: int, bos_every: int) -> np.ndarray:
    u = rng.random(shape)
    ids = (u * u * (vocab - 2)).astype(np.int32) + 2
    if bos_every:
        ids = np.where(rng.integers(0, bos_every, shape) == 0, 1, ids)
    return ids.astype(np.int32)


def train_batch(t: Dict, vocab: int, seed: int, step: int) -> Dict[str, np.ndarray]:
    """``t``: {"global_batch", "seq_len", "bos_every"}. Labels are the next
    tokens of one stream of ``seq_len + 1``."""
    rng = _rng(seed, TRAIN_STREAM, step)
    ids = tokens(rng, (t["global_batch"], t["seq_len"] + 1), vocab, t.get("bos_every", 0))
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

