"""Mesh partitioning rules — one shape-deterministic spec per pytree leaf.

Axis conventions (see ``repro.launch.mesh``):

  pod     pure data parallelism across pods (slowest links: only the
          per-step gradient all-reduce crosses them)
  data    batch dim of inputs; FSDP shard dim of params/optimizer state
  model   tensor parallelism (Megatron-style) + sequence parallelism for
          activations (``act_axes``)
  stage   GPipe pipeline stages (``repro.dist.pipeline``)

Rules are pure functions of (mesh, leaf shape) so params, optimizer moments
and checkpoint-restore targets always agree, and every assignment is
divisibility-checked — a spec produced here never makes GSPMD pad.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_BATCH_AXES = ("pod", "data")
_MODEL_AXIS = "model"
_FSDP_AXIS = "data"


def _axis_size(mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.axis_names else 0


def _trim(entries) -> P:
    entries = list(entries)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_spec(mesh, shape: Tuple[int, ...]) -> P:
    """Partition spec for a parameter-like leaf.

    The largest dim divisible by the model-axis size is tensor-parallel;
    the largest remaining dim divisible by the data-axis size is
    FSDP-sharded.  Dims of 1 and scalars stay replicated; the pod axis
    never shards parameters (pure DP across pods).
    """
    if not shape:
        return P()
    entries: list = [None] * len(shape)
    by_size = sorted(range(len(shape)), key=lambda i: -shape[i])
    model = _axis_size(mesh, _MODEL_AXIS)
    if model > 1:
        for i in by_size:
            if shape[i] > 1 and shape[i] % model == 0:
                entries[i] = _MODEL_AXIS
                break
    fsdp = _axis_size(mesh, _FSDP_AXIS)
    if fsdp > 1:
        for i in by_size:
            if entries[i] is None and shape[i] > 1 and shape[i] % fsdp == 0:
                entries[i] = _FSDP_AXIS
                break
    return _trim(entries)


def batch_spec(mesh, shape: Tuple[int, ...]) -> P:
    """Partition spec for a host-batch leaf: leading dim over (pod, data).

    Falls back to data-only, then to replication, whenever the batch size
    does not divide — small smoke batches on big meshes must still run.
    """
    if not shape:
        return P()
    axes = tuple(a for a in _BATCH_AXES if _axis_size(mesh, a) > 0)
    rest = [None] * (len(shape) - 1)
    if axes:
        size = 1
        for a in axes:
            size *= _axis_size(mesh, a)
        if shape[0] % size == 0:
            return _trim([axes[0] if len(axes) == 1 else axes] + rest)
        if _FSDP_AXIS in axes and shape[0] % _axis_size(mesh, _FSDP_AXIS) == 0:
            return _trim([_FSDP_AXIS] + rest)
    return P()


def act_axes(mesh) -> Optional[Tuple[Any, Any]]:
    """(batch_axes, seq_axes) for residual-stream constraints (Megatron-SP).

    Returned value lands in ``ModelConfig.act_pspec`` and is consumed by
    ``models.attention`` at block boundaries; None when the mesh has no
    relevant axes (single device / CPU smoke)."""
    batch = tuple(a for a in _BATCH_AXES if _axis_size(mesh, a) > 0)
    b_ax: Any = batch[0] if len(batch) == 1 else (batch or None)
    s_ax = _MODEL_AXIS if _axis_size(mesh, _MODEL_AXIS) > 1 else None
    if b_ax is None and s_ax is None:
        return None
    return (b_ax, s_ax)


# ----------------------------------------------------------------------------
# Tree-level helpers (leaves need only .shape — arrays or ShapeDtypeStructs)
# ----------------------------------------------------------------------------

def _leaf_sharding(mesh, leaf, rule) -> NamedSharding:
    shape = tuple(getattr(leaf, "shape", ()))
    return NamedSharding(mesh, rule(mesh, shape))


def params_shardings(mesh, params):
    """NamedSharding tree for model parameters (TP + FSDP)."""
    return jax.tree.map(lambda l: _leaf_sharding(mesh, l, param_spec), params)


def opt_state_shardings(mesh, opt_state):
    """Optimizer state mirrors the parameter rule (moments share shapes);
    step counters and other scalars come out replicated."""
    return jax.tree.map(lambda l: _leaf_sharding(mesh, l, param_spec), opt_state)


def batch_shardings(mesh, batch):
    """NamedSharding tree for a host batch (leading dim = global batch)."""
    return jax.tree.map(lambda l: _leaf_sharding(mesh, l, batch_spec), batch)
