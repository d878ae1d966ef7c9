"""Background system poller + GC-pause watcher.

The poller is the rusage/plugin-style *asynchronous* metric source of the
paper's measurement model: a daemon thread samples RSS, the traced Python
heap, and the open-fd count on a configurable period, producing timelines
on the same ``perf_counter_ns`` timebase as region events (so the export
engine can clock-align them as Perfetto counter tracks).

Timelines are bounded: when a series reaches ``max_samples`` the poller
halves the series (keeping every other point) and doubles its period, so a
week-long run costs the same memory as a minute-long one.

GC pauses come from ``gc.callbacks`` — the interpreter invokes the
callback synchronously around each collection, so the delta between the
"start" and "stop" phases is the actual stop-the-world pause.  The
measurement owns one watcher; while a profiler session is on it also writes
each pause into the profiler's trace as a ``repro/gc`` span.
"""

from __future__ import annotations

import gc
import threading
import time
import tracemalloc
from typing import Dict, List, Optional

from .sysinfo import open_fd_count, rss_bytes, rss_source

GC_SPAN = "repro/gc"


class SystemPoller:
    """Daemon sampling thread for RSS / traced-heap / fd timelines."""

    def __init__(self, period_s: float = 0.1, max_samples: int = 1 << 14):
        self.period_s = max(float(period_s), 1e-3)
        self.max_samples = max(int(max_samples), 16)
        self.rss: List[List[int]] = []  # [t_perf_ns, bytes]
        self.heap: List[List[int]] = []  # [t_perf_ns, traced bytes]
        self.fds: List[List[int]] = []  # [t_perf_ns, open fds]
        self.peak_rss = 0
        self.peak_fds = 0
        self.n_samples = 0
        self.rss_source = "none"
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def sample(self) -> None:
        """Take one sample (also called directly at open/close so even a
        run shorter than the period gets endpoints)."""
        t = time.perf_counter_ns()
        rss = rss_bytes()
        self.rss_source = rss_source()
        self.rss.append([t, rss])
        self.peak_rss = max(self.peak_rss, rss)
        if tracemalloc.is_tracing():
            self.heap.append([t, tracemalloc.get_traced_memory()[0]])
        fds = open_fd_count()
        if fds is not None:
            self.fds.append([t, fds])
            self.peak_fds = max(self.peak_fds, fds)
        self.n_samples += 1
        if len(self.rss) >= self.max_samples:
            self._decimate()

    def _decimate(self) -> None:
        """Halve the timelines and double the period (bounded memory)."""
        self.rss = self.rss[::2]
        self.heap = self.heap[::2]
        self.fds = self.fds[::2]
        self.period_s *= 2

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="repro-memsys-poller", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.period_s):
            self.sample()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=2.0)
        self._thread = None
        self.sample()  # closing endpoint


class GcWatcher:
    """Accumulates GC pause time / counts via ``gc.callbacks``.

    ``span``: the profiler's annotation class (``jax.profiler.
    TraceAnnotation``) once the measurement has one, else None; a pause is
    a ``repro/gc`` span when ``span.is_enabled()``."""

    def __init__(self, max_samples: int = 1 << 12):
        self.max_samples = max(int(max_samples), 16)
        self.pauses: List[List[int]] = []  # [t_perf_ns (at stop), pause_ns]
        self.collections = 0
        self.collected = 0
        self.uncollectable = 0
        self.pause_ns_total = 0
        self.per_generation: Dict[int, Dict[str, int]] = {}
        self._t0 = 0
        self._installed = False
        self.span = None
        self._open_span = None

    def _callback(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            span = self.span
            if span is not None and span.is_enabled():
                self._open_span = span(GC_SPAN)
                self._open_span.__enter__()
            self._t0 = time.perf_counter_ns()
            return
        now = time.perf_counter_ns()
        if self._open_span is not None:
            self._open_span.__exit__(None, None, None)
            self._open_span = None
        pause = now - self._t0 if self._t0 else 0
        self._t0 = 0
        self.collections += 1
        self.pause_ns_total += pause
        self.collected += int(info.get("collected", 0))
        self.uncollectable += int(info.get("uncollectable", 0))
        gen = int(info.get("generation", 0))
        agg = self.per_generation.setdefault(
            gen, {"collections": 0, "pause_ns": 0, "collected": 0}
        )
        agg["collections"] += 1
        agg["pause_ns"] += pause
        agg["collected"] += int(info.get("collected", 0))
        if len(self.pauses) < self.max_samples:
            self.pauses.append([now, pause])

    def install(self) -> None:
        if not self._installed:
            gc.callbacks.append(self._callback)
            self._installed = True

    def uninstall(self) -> None:
        if self._installed:
            try:
                gc.callbacks.remove(self._callback)
            except ValueError:
                pass
            self._installed = False
