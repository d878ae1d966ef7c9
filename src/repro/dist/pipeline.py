"""GPipe pipeline parallelism over a ``stage`` mesh axis.

``pipeline_forward`` runs inside ``jax.shard_map`` with per-stage
parameters: microbatches stream through the stage ring via ``ppermute``,
one scan tick per schedule slot.  With M microbatches and S stages the
schedule is the classic GPipe trapezoid — M + S - 1 ticks, bubble fraction
(S-1)/(M+S-1).

The final outputs are collected with a masked psum so every stage returns
the same (replicated) result — callers can declare ``out_specs=P(None)``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def pipeline_forward(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,  # (M, microbatch, d) — replicated across stages
    n_stages: int,
    axis_name: str,
) -> jax.Array:
    """Stage-parallel forward; returns (M, microbatch, d), replicated.

    ``stage_fn(stage_params, x)`` applies THIS device's stage (params carry
    a leading length-1 stage dim from the shard_map split); its output shape
    must equal its input shape (it feeds the next stage's input).
    """
    m = microbatches.shape[0]
    stage = jax.lax.axis_index(axis_name)
    n_ticks = m + n_stages - 1
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(recv, t):
        # Stage 0 pulls from the microbatch queue; later stages consume what
        # the previous stage sent last tick.  Past the queue end stage 0
        # re-runs the last microbatch; those outputs can't reach the final
        # stage within the schedule, so they are never observed.
        queued = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, m - 1), axis=0, keepdims=False
        )
        x = jnp.where(stage == 0, queued, recv)
        y = stage_fn(stage_params, x)
        return jax.lax.ppermute(y, axis_name, ring), y

    # The carry holds what ppermute returns, which varies over the stage
    # axis; the initial value must be typed the same way.
    init = jax.lax.pcast(jnp.zeros_like(microbatches[0]), axis_name, to="varying")
    _, ys = jax.lax.scan(tick, init, jnp.arange(n_ticks))

    # Final stage finishes microbatch i at tick i + (S-1); mask + psum
    # replicates the result across the stage axis.
    tail = jax.lax.slice_in_dim(ys, n_stages - 1, n_stages - 1 + m, axis=0)
    out = jnp.where(stage == n_stages - 1, tail, jnp.zeros_like(tail))
    return jax.lax.psum(out, axis_name)


def bubble_fraction(n_microbatches: int, n_stages: int) -> float:
    """Idle fraction of the GPipe schedule (monitoring aid)."""
    return (n_stages - 1) / max(n_microbatches + n_stages - 1, 1)
