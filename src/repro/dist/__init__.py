"""repro.dist — the distributed-monitoring layer.

Everything multi-process / multi-device routes through here so the core
monitoring layer can annotate events with a :class:`ProcessTopology`
instead of bare rank plumbing:

  sharding     mesh-axis partitioning rules (params / optimizer / batch)
  train        sharded train step + abstract (ShapeDtypeStruct) state
  serve        sharded prefill / decode + continuous batching slots
  compression  int8 all-reduce and top-k error-feedback gradient compression
  pipeline     GPipe stage-parallel forward over a 'stage' mesh axis
  straggler    per-step watchdog feeding the metrics substrate

Submodules import lazily (``from repro.dist import train``) so that
importing the package does not initialize jax device state, so a caller
can still set XLA_FLAGS (a forced host device count) after importing it.
"""

from repro.core.topology import ProcessTopology  # noqa: F401

__all__ = ["ProcessTopology"]
