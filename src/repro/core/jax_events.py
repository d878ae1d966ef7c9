"""JAX integration: model-layer names on device work, the monitor's host
spans on the profiler's clock, and AOT cost numbers.

The paper instruments MPI/pthread/CUDA activity alongside Python regions.
Device work under XLA is compiled, so there is no per-kernel host callback;
three things stand in for one:

- ``scope(name)`` names a model layer with ``jax.named_scope``. The name
  survives ``jax.checkpoint``, ``lax.scan`` and transposition into the
  optimized HLO's ``op_name`` metadata, so a profiler trace's device
  operations can be charged to the layer (recomputation carries
  ``rematted_computation`` in the same path). ``LAYER_SCOPES`` is the table
  of names.
- ``JaxBridge`` is what a measurement adds once ``jax`` is imported: while a
  profiler session is on, user regions and GC pauses are written into the
  profiler's own trace as ``jax.profiler.TraceAnnotation`` spans, on the
  device trace's clock; and JAX's compile events become ``jax.compile.*``
  metrics on the monitor's clock.
- ``compiled_metrics`` / ``collective_stats`` read AOT cost numbers.
"""

from __future__ import annotations

import re
from typing import Any, Dict

try:  # jax is an optional dependency of the core (monitoring works without it)
    import jax
    import jax.monitoring
    from jax.profiler import TraceAnnotation
except Exception:  # pragma: no cover
    jax = None
    TraceAnnotation = None

#: The model layers that device time is charged to, by ``jax.named_scope``
#: name, and where each is applied on the train path.
LAYER_SCOPES: Dict[str, str] = {
    "embed": "models/lm.py _embed_tokens: token embedding lookup",
    "norm": "models/transformer.py norm_apply: RMS and layer norms",
    "layer_stack": "models/transformer.py stack_apply: the group scan's own slicing, stacking and carries",
    "ssd_proj": "models/ssd.py ssd_apply outside the chunk scan: in_proj split, conv, gate, norm, out_proj",
    "ssd_scan": "models/ssd.py ssd_apply: the chunked SSD scan, jnp or Pallas",
    "attn_proj": "models/attention.py gqa_apply: qkv projection, rope, output projection",
    "attn_core": "models/attention.py gqa_apply: scores, softmax and PV, chunked, naive or flash",
    "mlp": "models/transformer.py _ffn_apply: the dense feed-forward",
    "head_loss": "models/lm.py lm_loss: logits and cross entropy",
    "param_cast": "dist/train.py _cast_params_for_compute: mixed-precision weight cast",
    "optimizer": "optim/adamw.py update: clip, moments, step",
}

#: JAX compile events (``jax.monitoring`` durations) and the monitor metric
#: each becomes. ``backend_s`` wraps ``compile_or_get_cached``, so it holds a
#: persistent-cache hit's load, which ``cache_load_s`` reports on its own.
COMPILE_EVENTS: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "jax.compile.backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.compile.cache_load_s",
}

#: Prefix of the monitor's spans in a profiler trace (GC pauses: ``repro/gc``,
#: memsys.poller).
SPAN_PREFIX = "repro/"


def scope(name: str):
    """``jax.named_scope(name)`` for a model layer named in ``LAYER_SCOPES``."""
    if name not in LAYER_SCOPES:
        raise ValueError(f"{name!r} is not a layer scope; LAYER_SCOPES has {sorted(LAYER_SCOPES)}")
    return jax.named_scope(name)


class JaxBridge:
    """One measurement's hooks into a loaded ``jax``.

    ``span`` is the profiler's annotation class; callers gate every span on
    ``span.is_enabled()``, so with no profiler session on nothing is built.
    Created by the measurement once ``jax`` is imported; ``close`` removes
    the compile-event listener.
    """

    def __init__(self, measurement):
        self.span = TraceAnnotation
        self._m = measurement
        self._names: Dict[int, str] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        name = COMPILE_EVENTS.get(event)
        if name is not None:
            self._m.metric(name, duration)

    def region_span(self, rid: int):
        """Open and return the span ``repro/<module>/<name>`` of user region ``rid``."""
        name = self._names.get(rid)
        if name is None:
            region = self._m.regions.get(rid)
            name = self._names[rid] = f"{SPAN_PREFIX}{region.module}/{region.name}"
        span = self.span(name)
        span.__enter__()
        return span

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


# ----------------------------------------------------------------------------
# AOT (compiled) artifact accounting.
# ----------------------------------------------------------------------------

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

# Matches the op *application* (name followed by its operand paren), sync or
# async: "all-reduce(...)", "all-reduce-start(...)", "all-gather-done(...)".
# Anchoring on "(" keeps lhs instruction names ("%all-reduce-start.1 = ...")
# and operand references ("...(%all-reduce-start.2)") from matching.
_HLO_OP_RE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)
# One "dtype[dims]" shape; async-start results are tuples of these.
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACES_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Parse per-collective byte counts from (post-SPMD) HLO text.

    Bytes are *wire-estimate* bytes: result-shape bytes scaled by the ring
    factor for the op and its replica-group size g —
    all-reduce 2(g-1)/g, all-gather/reduce-scatter (g-1)/g, all-to-all
    (g-1)/g, collective-permute 1.

    Async forms are handled: ``*-start`` ops count (their result tuple's
    largest element is the transferred buffer — for all-gather-start the
    tuple is (input, output) and the gathered output is the byte count that
    matches the sync form), while the paired ``*-done`` ops are skipped so
    an async-ified collective is counted exactly once.
    """
    out: Dict[str, Dict[str, float]] = {
        op: {"count": 0, "result_bytes": 0.0, "wire_bytes": 0.0} for op in _COLLECTIVES
    }
    for line in hlo_text.splitlines():
        match = _HLO_OP_RE.search(line)
        if not match:
            continue
        op, suffix = match.group(1), match.group(2)
        if suffix == "-done":
            continue  # completion half of a counted *-start
        eq = line.find("=")
        if eq < 0 or eq > match.start():
            continue  # operand reference, not an instruction result
        shapes = _SHAPE_RE.findall(line[eq + 1 : match.start()])
        if not shapes:
            continue
        sizes = [_shape_bytes(dtype, dims) for dtype, dims in shapes]
        # Async-start result tuples: the element matching the sync form's
        # result is the largest (all-gather's gathered output; all-reduce /
        # collective-permute buffers dwarf the u32[] context scalars) —
        # except reduce-scatter, whose scattered result is the *smallest*
        # real shape, so max() would overcount by the group-size factor.
        nbytes = min(sizes) if op == "reduce-scatter" else max(sizes)
        g = _group_size(line)
        if op == "all-reduce":
            factor = 2.0 * (g - 1) / g if g > 1 else 0.0
        elif op in ("all-gather", "reduce-scatter", "all-to-all"):
            factor = (g - 1) / g if g > 1 else 0.0
        else:  # collective-permute
            factor = 1.0
        rec = out[op]
        rec["count"] += 1
        rec["result_bytes"] += nbytes
        rec["wire_bytes"] += nbytes * factor
    return out


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        # iota format [n_groups, group_size]<=[...]
        return int(m.group(2))
    m = _GROUPS_BRACES_RE.search(line)
    if m:
        # explicit format {{0,1,2,3},{...}} — first group's cardinality
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


def compiled_metrics(compiled: Any) -> Dict[str, float]:
    """Extract flops / bytes / collective bytes from a compiled executable."""
    cost = compiled.cost_analysis() or {}
    stats = collective_stats(compiled.as_text())
    coll_wire = sum(rec["wire_bytes"] for rec in stats.values())
    coll_count = sum(rec["count"] for rec in stats.values())
    mem = compiled.memory_analysis()
    out = {
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_wire_bytes": float(coll_wire),
        "collective_ops": float(coll_count),
    }
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            out[attr] = float(getattr(mem, attr, 0) or 0)
    return out

