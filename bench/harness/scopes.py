"""Reduction of one JAX profiler trace to device time by model layer, and
of the device's idle time to the host span open during it.

The program names its model layers with ``jax.named_scope`` names from
``repro.core.jax_events.LAYER_SCOPES``; the names reach the optimized HLO's
``op_name`` metadata, and the trace names each device operation by its HLO
instruction.

- Each device operation inside the ``bench.window`` span (on the host's
  clock, as ``trace.load`` puts it) is taken at its self time, as
  ``trace.self_times`` gives it, and charged to the innermost layer scope in
  the ``op_name`` that the step's HLO text gives the instruction. A fusion
  carries its own metadata. An operation with no scope in its ``op_name``
  is unscoped; one under ``rematted_computation`` is also recomputation.
- Counted apart, and both should be 0: operations whose name the HLO text
  lacks, and operations of other programs (an "XLA Modules" event of
  another name, or none, holds them).
- Idle time is split by the innermost ``repro/`` span the monitor had open
  on the host (``repro/gc``, ``repro/train/train_step``), else by the
  innermost benchmark span, as ``trace.label_at`` names it; the longest
  gaps are listed with their start in the window and their split.

Per-scope self times plus the unscoped time add up exactly to the window's
total operation self time (integer nanoseconds, summed over devices).
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace

HOST_PREFIX = "repro/"
RECOMPUTE = "rematted_computation"
UNSCOPED = "unscoped"

# "%fusion.12 = f32[...] fusion(...), ..., metadata={op_name="a/b" ...}"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
# one op_name component: a scope, bare or inside transforms ("jvp(embed)",
# "transpose(jvp(layer_stack))"); "jit(norm)" names a jitted function, not a scope
_PART = re.compile(r"^((?:\w+\()*)(\w+)\)*$")


def layer_scopes() -> Optional[Tuple[str, ...]]:
    """The program's table of layer scope names; None where it has none."""
    try:
        from repro.core.jax_events import LAYER_SCOPES
    except ImportError:
        return None
    return tuple(LAYER_SCOPES)


def hlo_op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: op_name metadata or ""})."""
    module = ""
    names: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTR.match(line)
        if m:
            meta = _OP_NAME.search(line)
            names[m.group(1)] = meta.group(1) if meta else ""
    return module, names


def layer_of(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The innermost scope of ``scopes`` among the components of ``op_name``."""
    for part in reversed(op_name.split("/")):
        m = _PART.match(part)
        if m and m.group(2) in scopes and "jit(" not in m.group(1):
            return m.group(2)
    return None


def _load(path: str):
    """Per device, in ``trace.load``'s order: the "XLA Modules" events as
    (module name, start, end) on the device's clock; and the monitor's host
    spans as (name, start, end)."""
    from jax.profiler import ProfileData

    modules: List[List[Tuple[str, int, int]]] = []
    host: List[trace.Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            mods = []
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        mods.append((ev.name.split("(", 1)[0], s, s + int(ev.duration_ns)))
            modules.append(sorted(mods, key=lambda m: m[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns)))
    return modules, host


def _module_at(mods: List[Tuple[str, int, int]], starts: List[int], t: int) -> Optional[str]:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < mods[i][2]:
        return mods[i][0]
    return None


def idle_label(host: List[trace.Event], spans: List[trace.Event], t: int) -> str:
    """The innermost monitor span open at t, else the benchmark's."""
    best: Optional[trace.Event] = None
    for name, s, e in host:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    if best is not None:
        return best[0]
    label = trace.label_at(spans, t)
    return label if label == "outside spans" else trace.SPAN_PREFIX + label


def split_idle(gaps: List[trace.Interval], host: List[trace.Event],
               spans: List[trace.Event]) -> Dict[str, int]:
    """Idle nanoseconds by the span open on the host, cut at span edges."""
    edges = sorted({t for _, s, e in host + spans for t in (s, e)})
    out: Dict[str, int] = defaultdict(int)
    for lo, hi in gaps:
        cuts = [lo] + [t for t in edges if lo < t < hi] + [hi]
        for a, b in zip(cuts, cuts[1:]):
            out[idle_label(host, spans, (a + b) // 2)] += b - a
    return dict(out)


def reduce(path: str, hlo_text: str, scopes: Iterable[str], top: int = 10) -> Dict:
    """Device time of the step's program in the window, by layer scope."""
    scopes = frozenset(scopes)
    module, op_names = hlo_op_names(hlo_text)
    devices, spans = trace.load(path)
    modules, host = _load(path)
    windows = [(s, e) for n, s, e in spans if n == trace.WINDOW]
    if not devices or len(windows) != 1 or len(modules) != len(devices):
        raise ValueError(f"{path}: {len(devices)} TPU planes, {len(windows)} windows")
    lo, hi = windows[0]
    by_scope: Dict[str, int] = defaultdict(int)
    by_op: Dict[str, int] = defaultdict(int)
    recompute: Dict[str, int] = defaultdict(int)
    not_in_hlo = other_programs = 0
    missing: Dict[str, int] = defaultdict(int)
    for d, mods in zip(devices, modules):
        starts = [s for _, s, _ in mods]
        inside = []
        for name, s, e in d["ops"]:
            if s < lo or e > hi:
                continue
            if _module_at(mods, starts, s - d["shift_ns"]) != module:
                other_programs += 1
            else:
                inside.append((name, s, e))
                not_in_hlo += name not in op_names
        for name, ns in trace.self_times(inside).items():
            op_name = op_names.get(name)
            if op_name is None:
                missing[name] += ns
                op_name = ""
            layer = layer_of(op_name, scopes) or UNSCOPED
            by_scope[layer] += ns
            if layer == UNSCOPED:
                by_op[name] += ns
            if RECOMPUTE in op_name:
                recompute[layer] += ns
    merged = [trace.union(trace.clip(d["programs"] or [(s, e) for _, s, e in d["ops"]], lo, hi))
              for d in devices]
    busy = sum(trace.covered(m, lo, hi) for m in merged)
    n = len(devices)
    total = sum(by_scope.values())
    host_in = [(name, s, e) for name, s, e in host if e > lo and s < hi]
    idle = trace.gaps(merged[0], lo, hi)
    host_time: Dict[str, List[float]] = {}
    for name, s, e in host_in:
        entry = host_time.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += (min(e, hi) - max(s, lo)) / 1e9
    return {
        "module": module,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "total_ns": total,
        "total_s": total / n / 1e9,
        "scope_ns": dict(by_scope),
        "scope_s": {k: v / n / 1e9 for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])},
        "recompute_s": sum(recompute.values()) / n / 1e9,
        "recompute_scope_s": {k: v / n / 1e9 for k, v in sorted(recompute.items(), key=lambda kv: -kv[1])},
        "not_in_hlo": not_in_hlo,
        "other_programs": other_programs,
        "unscoped_ops": [[k, v / n / 1e9] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "missing_ops": [[k, v / n / 1e9] for k, v in sorted(missing.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_span": {k: v / 1e9 for k, v in sorted(
            split_idle(idle, host_in, spans).items(), key=lambda kv: -kv[1])},
        "long_gaps": [[(g0 - lo) / 1e9, (g1 - g0) / 1e9,
                       {k: v / 1e9 for k, v in split_idle([(g0, g1)], host_in, spans).items()}]
                      for g0, g1 in sorted(idle, key=lambda g: g[0] - g[1])[:top]],
        "host_spans": host_time,
    }


def per_step(reduction: Dict, steps: int) -> Dict[str, float]:
    """The reduction as per-layer numbers: ``device_ms.<scope>`` in ms a
    step, ``device_unscoped`` and ``device_recompute`` in % of busy time."""
    out = {f"device_ms.{k}": 1e3 * v / steps for k, v in reduction["scope_s"].items()
           if k != UNSCOPED}
    busy = reduction["busy_s"]
    out["device_unscoped"] = 100.0 * reduction["scope_s"].get(UNSCOPED, 0.0) / busy
    out["device_recompute"] = 100.0 * reduction["recompute_s"] / busy
    return out
