"""Reduction of one JAX profiler trace (``.xplane.pb``) to device busy time,
the top device operations and the idle gaps labelled by host spans.

The benchmark opens host spans with ``jax.profiler.TraceAnnotation`` named
``bench.<what>``; the span ``bench.window`` is the traced window.

- Busy time is the union, inside the window, of the intervals in which a
  program ran on a device: the events of each ``/device:TPU:<n>`` plane's
  "XLA Modules" line. Averaged over the devices.
- Operations are the events of the "XLA Ops" line, named by their HLO
  instruction name. A loop's event holds its body's events, so each
  operation is charged its self time: its duration less that of the events
  nested in it.
- The device's clock is put on the host's: a program cannot start before
  the host enqueued it, so each device's events are shifted by the largest
  (host ``DoEnqueueProgram`` start - device program start) over the
  programs both sides name by ``run_id``.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ENQUEUE = "DoEnqueueProgram"

Interval = Tuple[int, int]
Event = Tuple[str, int, int]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(merged: List[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in clip(merged, lo, hi))


def gaps(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(hlo_text: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def self_times(ops: List[Event]) -> Dict[str, int]:
    """Self time per operation name of possibly nested events of one line."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[Tuple[str, int]] = []  # (name, end) of the open events
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= e - s
        out[name] += e - s
        stack.append((name, e))
    return dict(out)


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str):
    """Per device: (program intervals, op events), on the host's clock; and
    the host's benchmark spans. Events are (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw = []
    spans: List[Event] = []
    enqueue: Dict[int, int] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            modules, ops = [], []
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if line.name == MODULES_LINE:
                        modules.append((_stat(ev, "run_id"), s, e))
                    elif line.name == OPS_LINE:
                        ops.append((op_name(ev.name), s, e))
            raw.append((modules, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
                    elif ev.name == ENQUEUE:
                        rid = _stat(ev, "run_id")
                        if rid is not None:
                            enqueue[int(rid)] = min(s, enqueue.get(int(rid), s))
    devices = []
    for modules, ops in raw:
        shifts = [enqueue[int(r)] - s for r, s, _ in modules if r is not None and int(r) in enqueue]
        d = max(shifts) if shifts else 0
        devices.append({
            "programs": [(s + d, e + d) for _, s, e in modules],
            "ops": [(n, s + d, e + d) for n, s, e in ops],
            "shift_ns": d,
        })
    return devices, spans


def label_at(spans: List[Event], t: int) -> str:
    """The innermost benchmark span (other than the window) open at t."""
    best: Optional[Event] = None
    for name, s, e in spans:
        if name != WINDOW and s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "outside spans"


def reduce(path: str, top: int = 10) -> Dict:
    devices, spans = load(path)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not devices:
        raise ValueError(f"{path}: no TPU plane")
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} '{WINDOW}' spans, want 1")
    lo, hi = windows[0]
    merged = [union(clip(d["programs"] or [(s, e) for _, s, e in d["ops"]], lo, hi)) for d in devices]
    busy = [covered(m, lo, hi) for m in merged]
    by_op: Dict[str, int] = defaultdict(int)
    for d in devices:
        inside = [(n, s, e) for n, s, e in d["ops"] if s >= lo and e <= hi]
        for name, ns in self_times(inside).items():
            by_op[name] += ns
    n = len(devices)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(merged[0], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "n_devices": n,
        "n_programs": sum(len(d["programs"]) for d in devices),
        "n_ops": sum(len(d["ops"]) for d in devices),
        "clock_shift_ns": [d["shift_ns"] for d in devices],
        "device_ops": [[name, ns / n / 1e9] for name, ns in top_ops],
        "idle_gaps": [[label_at(spans, (s + e) // 2), (e - s) / 1e9] for s, e in idle],
    }

