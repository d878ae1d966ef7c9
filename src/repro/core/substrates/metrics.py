"""Metrics substrate — counters and per-step series (Score-P metric plugins).

Collects user metrics (``repro.core.metric(name, value)``) as time series and
aggregates: the launch drivers' per-step times, losses and RSS, and, once
``jax`` is imported, JAX's compile events as ``jax.compile.*`` durations
(``repro.core.jax_events.JaxBridge``).  Events themselves are summarized
only by count (cheap).

Non-finite metric values (a NaN loss is a fact of life in training) must not
poison the artifacts: aggregates are computed over the finite samples (with a
``nonfinite`` count alongside), series entries serialize non-finite values as
``null``, and ``metrics.json`` is written with ``allow_nan=False`` so it is
always strictly-parseable JSON (bare ``NaN``/``Infinity`` are not JSON).
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional

import numpy as np

from ..schema import stamp
from .base import Substrate


def _finite_or_none(value: float) -> Optional[float]:
    return float(value) if math.isfinite(value) else None


class MetricsSubstrate(Substrate):
    name = "metrics"

    def __init__(self, keep_series: bool = True):
        self.keep_series = keep_series
        self._series: Dict[str, List] = {}
        self._agg: Dict[str, Dict[str, float]] = {}
        self._event_counts: Dict[int, int] = {}
        self._run_dir = ""
        self._meta: Dict[str, Any] = {}

    def open(self, run_dir: str, meta: Dict[str, Any]) -> None:
        self._run_dir = run_dir
        self._meta = meta

    def on_flush(self, thread_id: int, columns) -> None:
        n = int(len(columns["kind"]))
        self._event_counts[thread_id] = self._event_counts.get(thread_id, 0) + n

    def on_metric(self, name: str, value: float, t_ns: int) -> None:
        agg = self._agg.get(name)
        if agg is None:
            agg = self._agg[name] = {
                "count": 0, "nonfinite": 0, "sum": 0.0,
                "min": float("inf"), "max": float("-inf"),
            }
        agg["count"] += 1
        if math.isfinite(value):
            agg["sum"] += value
            agg["min"] = min(agg["min"], value)
            agg["max"] = max(agg["max"], value)
        else:
            agg["nonfinite"] += 1
        if self.keep_series:
            self._series.setdefault(name, []).append((t_ns, value))

    def summary(self) -> Dict[str, Dict[str, float]]:
        out = {}
        for name, agg in self._agg.items():
            finite = agg["count"] - agg["nonfinite"]
            entry = dict(agg, mean=agg["sum"] / finite if finite else None)
            if finite == 0:  # min/max stayed at their +-inf sentinels
                entry["min"] = entry["max"] = None
            series = self._series.get(name)
            if series:
                vals = np.asarray([v for _, v in series], dtype=np.float64)
                vals = vals[np.isfinite(vals)]
                if len(vals):
                    entry["median"] = float(np.median(vals))
                    entry["p99"] = float(np.percentile(vals, 99))
            out[name] = entry
        return out

    def close(self, region_table) -> None:
        doc = stamp({
            "meta": self._meta,
            "events_per_thread": {str(k): v for k, v in self._event_counts.items()},
            "metrics": self.summary(),
        })
        if self.keep_series:
            doc["series"] = {
                name: [[int(t), _finite_or_none(v)] for t, v in vals]
                for name, vals in self._series.items()
            }
        with open(os.path.join(self._run_dir, "metrics.json"), "w") as fh:
            json.dump(doc, fh, indent=1, allow_nan=False)
