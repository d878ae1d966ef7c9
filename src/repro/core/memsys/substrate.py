"""The ``memory`` measurement substrate — memory.json writer.

Composes the heap collector (per-region allocation attribution), the
system poller (RSS / heap / fd timelines), and the measurement's GC watcher
into one substrate.  Artifact:

    memory.json
      heap      per-region alloc/net bytes + blocks, per-thread peaks
      rss       peak/end + probe source
      gc        collections, pause totals, per-generation breakdown
      fds       peak/end open file descriptors
      series    counter timelines on the perf_counter_ns timebase
                (``mem.rss_mb``, ``mem.heap_mb``, ``mem.fds``,
                ``mem.gc_pause_ms``) — the export engine renders these as
                Perfetto counter tracks next to the metrics.json series.

Disabled by default; enabled via ``REPRO_MONITOR_MEMORY=1`` or by listing
``memory`` in the substrates.  When disabled no collector or poller is
installed and tracemalloc stays off, so the event fast path and the flush
path are untouched (the measurement's GC watcher is on either way).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from ..schema import stamp
from ..substrates.base import Substrate
from .heap import HeapCollector
from .poller import GcWatcher, SystemPoller

DEFAULT_PERIOD_S = 0.1
DEFAULT_TOPN = 25

ARTIFACT = "memory.json"


class MemorySubstrate(Substrate):
    name = "memory"

    def __init__(
        self,
        gc: GcWatcher,
        period: float = DEFAULT_PERIOD_S,
        topn: int = DEFAULT_TOPN,
        trace_python: bool = True,
    ):
        """``gc``: the measurement's watcher, installed and removed by the
        measurement; this substrate only reads it."""
        self.period = float(period)
        self.topn = int(topn)
        self.heap = HeapCollector(trace_python=trace_python)
        self.poller = SystemPoller(period_s=self.period)
        self.gc = gc
        self._run_dir = ""
        self._meta: Dict[str, Any] = {}

    def open(self, run_dir: str, meta: Dict[str, Any]) -> None:
        self._run_dir = run_dir
        self._meta = meta
        self.heap.open()
        self.poller.sample()  # opening endpoint even for sub-period runs
        self.poller.start()

    def on_flush(self, thread_id: int, columns) -> None:
        self.heap.on_flush(thread_id, columns)

    def close(self, region_table: List[Dict[str, Any]]) -> None:
        self.poller.stop()
        self.heap.close()
        doc = self.document(region_table)
        with open(os.path.join(self._run_dir, ARTIFACT), "w") as fh:
            json.dump(doc, fh, indent=1, allow_nan=False)

    # -- document assembly (separate so tests/tools can introspect) ---------

    def document(self, region_table: List[Dict[str, Any]]) -> Dict[str, Any]:
        heap_doc = self.heap.region_table(region_table, topn=self.topn)
        heap_doc.update(
            start_bytes=self.heap.start_bytes,
            end_bytes=self.heap.end_bytes,
            peak_bytes=self.heap.peak_bytes,
            threads=self.heap.thread_table(),
        )
        rss_series = self.poller.rss
        fd_series = self.poller.fds
        series = {
            "mem.rss_mb": [[t, v / 1e6] for t, v in rss_series],
            "mem.heap_mb": [[t, v / 1e6] for t, v in self.poller.heap],
            "mem.fds": [[t, float(v)] for t, v in fd_series],
            "mem.gc_pause_ms": [[t, p / 1e6] for t, p in self.gc.pauses],
        }
        return stamp({
            "meta": self._meta,
            "config": {"period_s": self.period, "topn": self.topn},
            "heap": heap_doc,
            "rss": {
                "peak_bytes": self.poller.peak_rss,
                "end_bytes": rss_series[-1][1] if rss_series else 0,
                "samples": self.poller.n_samples,
                "source": self.poller.rss_source,
            },
            "gc": {
                "collections": self.gc.collections,
                "pause_ns_total": self.gc.pause_ns_total,
                "collected": self.gc.collected,
                "uncollectable": self.gc.uncollectable,
                "per_generation": {
                    str(g): agg for g, agg in sorted(self.gc.per_generation.items())
                },
            },
            "fds": {
                "peak": self.poller.peak_fds,
                "end": fd_series[-1][1] if fd_series else None,
            },
            "series": {k: v for k, v in series.items() if v},
        })


def load_memory(run_dir: str) -> Optional[Dict[str, Any]]:
    """Read a run's memory.json (``None`` when the substrate was off or the
    artifact is unreadable — callers treat memory data as best-effort)."""
    path = os.path.join(run_dir, ARTIFACT)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


# -- stable document accessors ------------------------------------------------
#
# Every consumer of memory.json (analysis renderers, the HTML report, merge's
# cross-rank section) goes through these instead of indexing the raw dict, so
# the JSON layout can evolve behind one compatibility seam.  All of them
# tolerate missing sections (older writers, partial documents).


def region_rows(doc: Dict[str, Any], top: int = 0) -> List[Dict[str, Any]]:
    """Per-region allocation rows from a memory.json document, sorted by
    attributed alloc bytes descending.  ``top`` > 0 truncates.  Each row:
    ``{"region", "alloc_bytes", "net_bytes", "alloc_blocks", "flushes"}``."""
    regions = doc.get("heap", {}).get("regions", {})
    rows = [
        {
            "region": name,
            "alloc_bytes": int(row.get("alloc_bytes", 0)),
            "net_bytes": int(row.get("net_bytes", 0)),
            "alloc_blocks": int(row.get("alloc_blocks", 0)),
            "flushes": int(row.get("flushes", 0)),
        }
        for name, row in regions.items()
    ]
    rows.sort(key=lambda r: -r["alloc_bytes"])
    return rows[:top] if top > 0 else rows


def overview(doc: Dict[str, Any]) -> Dict[str, Any]:
    """Scalar summary of a memory.json document (heap/rss/gc/fds headline
    numbers) with every field present regardless of writer age."""
    heap = doc.get("heap", {})
    rss = doc.get("rss", {})
    gc = doc.get("gc", {})
    fds = doc.get("fds", {})
    return {
        "heap_start_bytes": int(heap.get("start_bytes", 0)),
        "heap_end_bytes": int(heap.get("end_bytes", 0)),
        "heap_peak_bytes": int(heap.get("peak_bytes", 0)),
        "dropped_regions": int(heap.get("dropped_regions", 0) or 0),
        "rss_peak_bytes": int(rss.get("peak_bytes", 0)),
        "rss_end_bytes": int(rss.get("end_bytes", 0)),
        "rss_samples": int(rss.get("samples", 0)),
        "rss_source": rss.get("source", "?"),
        "gc_collections": int(gc.get("collections", 0)),
        "gc_pause_ns_total": int(gc.get("pause_ns_total", 0)),
        "gc_collected": int(gc.get("collected", 0)),
        "fds_peak": fds.get("peak"),
    }


def timelines(doc: Dict[str, Any]) -> Dict[str, List[List[float]]]:
    """The ``mem.*`` counter series of a memory.json document as
    ``{name: [[t_ns, value], ...]}`` (empty when series were not kept)."""
    return {k: v for k, v in doc.get("series", {}).items() if v}


def reclaim_rows(doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per-region allocation/reclaim columns for leak analysis, sorted by
    alloc bytes descending.  Each row: ``{"region", "alloc_bytes",
    "freed_bytes", "net_bytes", "reclaim_rate"}`` where ``reclaim_rate`` is
    ``freed / alloc`` (1.0 when the region allocated nothing — nothing to
    reclaim is fully reclaimed).  The fleet leak detector's seam into
    memory.json; keep it in sync with :func:`region_rows`."""
    regions = doc.get("heap", {}).get("regions", {})
    rows = []
    for name, row in regions.items():
        alloc = int(row.get("alloc_bytes", 0))
        freed = int(row.get("freed_bytes", 0))
        rows.append(
            {
                "region": name,
                "alloc_bytes": alloc,
                "freed_bytes": freed,
                "net_bytes": int(row.get("net_bytes", alloc - freed)),
                "reclaim_rate": (freed / alloc) if alloc > 0 else 1.0,
            }
        )
    rows.sort(key=lambda r: (-r["alloc_bytes"], r["region"]))
    return rows
