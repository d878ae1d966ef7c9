"""JAX integration — step regions, device metrics, collective accounting.

The paper instruments MPI/pthread/CUDA activity alongside Python regions.
The XLA analogue: device work is compiled, so there is no per-kernel host
callback — instead we (a) tag host-side dispatch with user regions +
``jax.named_scope`` (region names survive into HLO metadata, the moral
equivalent of Score-P's region handles crossing the language boundary),
and (b) attach AOT cost-model numbers (FLOPs, bytes, per-collective bytes)
as metrics on the step region, giving profiles the device dimension the
paper gets from CUPTI.
"""

from __future__ import annotations

import re
import time
from contextlib import contextmanager
from functools import wraps
from typing import Any, Callable, Dict, Optional

from . import measurement as _m

try:  # jax is an optional dependency of the core (monitoring works without it)
    import jax
except Exception:  # pragma: no cover
    jax = None


@contextmanager
def annotate(name: str):
    """Host region + XLA named scope in one context manager."""
    if jax is None:
        with _m.region(name, module="jax"):
            yield
        return
    with _m.region(name, module="jax"), jax.named_scope(name):
        yield


def instrument_step(fn: Callable, name: str, *, block: bool = True) -> Callable:
    """Wrap a (possibly jitted) step function with host-side step regions.

    Records ``<name>`` as a region per call and a ``<name>.ms`` metric.  With
    ``block=True`` the wrapper calls ``block_until_ready`` on the result so
    the region covers device execution, not just dispatch (async dispatch
    would otherwise make steps look free — the JAX-flavored pitfall of the
    paper's host-side methodology).
    """

    @wraps(fn)
    def wrapper(*args, **kwargs):
        m = _m.active()
        if m is None:
            return fn(*args, **kwargs)
        t0 = time.perf_counter_ns()
        with m.region(name, module="jax.step"):
            out = fn(*args, **kwargs)
            if block and jax is not None:
                out = jax.block_until_ready(out)
        m.metric(f"{name}.ms", (time.perf_counter_ns() - t0) / 1e6)
        return out

    return wrapper


# ----------------------------------------------------------------------------
# AOT (compiled) artifact accounting — also reused by the roofline harness.
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
    (g-1)/g, collective-permute 1.  Conventions documented in DESIGN.md §7.

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


def record_compiled(name: str, compiled: Any) -> Dict[str, float]:
    """Attach compiled-artifact metrics to the active measurement."""
    metrics = compiled_metrics(compiled)
    m = _m.active()
    if m is not None:
        for key, value in metrics.items():
            m.metric(f"{name}.{key}", value)
    return metrics
