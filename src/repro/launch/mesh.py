"""Mesh construction.  Functions, not module constants — importing this
module never touches jax device state."""

from __future__ import annotations

from typing import Optional

import jax


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
