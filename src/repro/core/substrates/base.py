"""Substrate plugin API.

Score-P fans measurement events out to "substrates" (profiling, tracing,
plugins for online interpretation).  Substrates here receive *batched*
event flushes as numpy columns — per-event work in the instrumentation fast
path is limited to one buffer append; everything expensive happens at flush
granularity.  (Score-P builds profiles online per event; our deferred design
is a deliberate overhead optimization.)
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from typing import Any, Dict, List

import numpy as np


class Substrate(ABC):
    """Receives event batches and definition tables; writes artifacts."""

    name: str = "?"

    @abstractmethod
    def open(self, run_dir: str, meta: Dict[str, Any]) -> None:
        """Called once before any events; ``meta`` holds process/clock info."""

    @abstractmethod
    def on_flush(self, thread_id: int, columns: Dict[str, np.ndarray]) -> None:
        """Receive one flushed batch of events from one thread (in order)."""

    def on_metric(self, name: str, value: float, t_ns: int) -> None:
        """Receive one user metric sample (counters, FLOPs, bytes, ...)."""

    @abstractmethod
    def close(self, region_table: List[Dict[str, Any]]) -> None:
        """Flush artifacts; called once at finalize with the region table."""
