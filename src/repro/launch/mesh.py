"""Mesh construction.  Functions, not module constants — importing this
module never touches jax device state (required by the dry-run contract)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """The production mesh: one pod = 16x16 = 256 chips (v5e), two pods for
    the multi-pod dry-run.  'pod' composes with 'data' for gradient
    reduction (pure DP across pods: inter-pod links are the slowest, so only
    per-step gradient all-reduce crosses them)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_elastic_mesh(n_devices: Optional[int] = None, model_parallel: int = 16):
    """Elastic-scaling helper: build the largest (data, model) mesh available.

    Used on restart after losing hosts: model_parallel stays fixed (weights
    reshard cleanly), the data axis absorbs whatever is left."""
    devices = jax.devices()
    n = n_devices or len(devices)
    model = min(model_parallel, n)
    while n % model:
        model //= 2
    data = n // model
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=devices[: data * model],
    )


def make_pipeline_mesh(n_stages: int, n_data: int):
    """Mesh with an explicit 'stage' axis for GPipe pipeline parallelism."""
    return jax.make_mesh(
        (n_data, n_stages), ("data", "stage"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def describe(mesh) -> str:
    return f"mesh{tuple(mesh.shape.values())} axes={mesh.axis_names} devices={mesh.devices.size}"


def elastic_setup(cfg, topology, use_mesh: bool):
    """Common driver bootstrap: resolve the elastic mesh (when requested),
    install activation sharding on the config, and bind the mesh shape into
    the topology.  A mesh requested with one visible device is an error,
    not a silent unsharded run.

    Returns ``(cfg, mesh, mesh_ctx, topology)`` where ``mesh`` is None on
    the unsharded path and ``mesh_ctx()`` yields the context the jitted
    step must be *called* under — activation PartitionSpec constraints
    resolve against the ambient mesh at trace time, not jit-creation time.
    """
    import contextlib

    from repro.dist.train import with_act_sharding

    if use_mesh:
        if len(jax.devices()) < 2:
            raise ValueError("--mesh needs more than one visible device; found 1")
        mesh = make_elastic_mesh()
        return with_act_sharding(cfg, mesh), mesh, (lambda: mesh), topology.with_mesh(mesh)
    return cfg, None, contextlib.nullcontext, topology
