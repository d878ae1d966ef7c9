"""Faults planted under a run, for the tests and the chip readings that
show each check can fail: hooks for the run functions in this directory."""

from __future__ import annotations

from typing import Any, Callable, Dict


def unchanged_state(step: Callable) -> Callable:
    """A train step that computes its stats but returns its state as given."""
    import jax
    import jax.numpy as jnp

    def faulty(params, opt_state, batch):
        copy = lambda t: jax.tree.map(jnp.copy, t)
        _, _, stats = step(copy(params), copy(opt_state), batch)
        return params, opt_state, stats

    return faulty


def half_batch(batch_fn: Callable) -> Callable:
    """Drops the second half of each batch's rows: the step's mean is then
    taken over the rest."""

    def faulty(i):
        b = batch_fn(i)
        n = b["tokens"].shape[0]
        if n < 2:
            raise ValueError("half_batch needs at least 2 rows")
        return {k: v[: n // 2] for k, v in b.items()}

    return faulty


TRAIN_FAULTS: Dict[str, Dict[str, Any]] = {
    "unchanged_state": {"wrap_step": unchanged_state},
    "half_batch": {"wrap_batch": half_batch},
}

