"""Per-thread event buffers — the "C-bindings" analogue.

Score-P's C bindings exist to make the per-event path as cheap as possible.
In a pure-CPython environment the equivalent engineering decision is *which
append primitive is cheapest*.  Two strategies are provided and benchmarked
(``benchmarks/event_throughput.py``); the list strategy wins on CPython
(``list.append`` is a single C call) and is the default.

Event record: ``(kind, region, t_ns, aux)``
  kind   u1   see ``EV_*`` constants
  region i4   region handle (``regions.FILTERED`` events are never appended)
  t_ns   u8   ``time.perf_counter_ns()``
  aux    u4   line number for LINE events, else 0

Buffers flush to the measurement manager (which fans out to substrates) when
``flush_threshold`` records accumulate, keeping memory bounded.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

# Event kinds.
EV_ENTER = 0
EV_EXIT = 1
EV_C_ENTER = 2
EV_C_EXIT = 3
EV_LINE = 4
EV_EXCEPTION = 5

EVENT_KIND_NAMES = {
    EV_ENTER: "enter",
    EV_EXIT: "exit",
    EV_C_ENTER: "c_enter",
    EV_C_EXIT: "c_exit",
    EV_LINE: "line",
    EV_EXCEPTION: "exception",
}

EventTuple = Tuple[int, int, int, int]

#: Column dtypes of a flushed batch.
COLUMNS = (("kind", np.uint8), ("region", np.int32), ("t", np.uint64), ("aux", np.uint32))


def columns_from_events(events: List[EventTuple]) -> Dict[str, np.ndarray]:
    """Convert a list of event tuples into named numpy columns."""
    if not events:
        return {name: np.empty(0, dtype=dt) for name, dt in COLUMNS}
    arr = np.asarray(events, dtype=np.uint64)
    return {
        "kind": arr[:, 0].astype(np.uint8),
        "region": arr[:, 1].astype(np.int64).astype(np.int32),
        "t": arr[:, 2],
        "aux": arr[:, 3].astype(np.uint32),
    }


class ListEventBuffer:
    """Default buffer: plain Python list of tuples (fastest append on CPython).

    Instrumenters bind ``self.events.append`` as a closure local; this class
    only manages flushing.
    """

    strategy = "list"

    def __init__(
        self,
        thread_id: int,
        flush_threshold: int = 1 << 16,
        on_flush: Optional[Callable[[int, Dict[str, np.ndarray]], None]] = None,
    ):
        self.thread_id = thread_id
        self.flush_threshold = flush_threshold
        self.on_flush = on_flush
        self.events: List[EventTuple] = []
        self.n_flushed = 0
        self._flushing = False

    def __len__(self) -> int:
        return len(self.events)

    def flush(self) -> None:
        # Identity of ``self.events`` must be preserved (instrumenter
        # closures bind ``events.append``), hence copy + in-place clear.
        # The _flushing guard stops recursion when flush work itself emits
        # events (flush can run in user context via region __exit__).
        if self._flushing or not self.events:
            return
        self._flushing = True
        try:
            batch = self.events[:]
            self.events.clear()
            self.n_flushed += len(batch)
            if self.on_flush is not None:
                self.on_flush(self.thread_id, columns_from_events(batch))
        finally:
            self._flushing = False


class NumpyEventBuffer:
    """Preallocated column-array buffer (Score-P-style fixed memory).

    Slower per event on CPython than :class:`ListEventBuffer` (four element
    stores vs one ``list.append``) but allocation-free in steady state; kept
    for the measured comparison in ``benchmarks/event_throughput.py``.
    """

    strategy = "numpy"

    #: Hard ceiling on growth, as a multiple of flush_threshold.  Events
    #: past it are dropped (counted in ``n_dropped``) rather than letting a
    #: flush-callback feedback loop grow the buffer without bound.
    MAX_GROWTH = 8

    def __init__(
        self,
        thread_id: int,
        flush_threshold: int = 1 << 16,
        on_flush: Optional[Callable[[int, Dict[str, np.ndarray]], None]] = None,
    ):
        self.thread_id = thread_id
        self.flush_threshold = flush_threshold
        self.on_flush = on_flush
        n = flush_threshold
        self._kind = np.empty(n, dtype=np.uint8)
        self._region = np.empty(n, dtype=np.int32)
        self._t = np.empty(n, dtype=np.uint64)
        self._aux = np.empty(n, dtype=np.uint32)
        self.cursor = 0
        self.n_flushed = 0
        self.n_dropped = 0
        self._flushing = False

    def __len__(self) -> int:
        return self.cursor

    @property
    def capacity(self) -> int:
        return self._kind.shape[0]

    def _grow(self) -> bool:
        """Double the column arrays in place; False once MAX_GROWTH is hit."""
        cap = self.capacity
        if cap >= self.flush_threshold * self.MAX_GROWTH:
            return False
        new_cap = min(cap * 2, self.flush_threshold * self.MAX_GROWTH)
        for name in ("_kind", "_region", "_t", "_aux"):
            old = getattr(self, name)
            arr = np.empty(new_cap, dtype=old.dtype)
            arr[:cap] = old
            setattr(self, name, arr)
        return True

    def append(self, kind: int, region: int, t: int, aux: int) -> None:
        i = self.cursor
        if i >= self.capacity:
            # Appends can outrun the preallocated columns when a flush is in
            # progress (the re-entrancy guard makes the threshold-triggered
            # flush a no-op, so the cursor keeps climbing): grow, or drop
            # once the growth ceiling is reached — never IndexError.
            if not self._grow():
                self.n_dropped += 1
                return
        self._kind[i] = kind
        self._region[i] = region
        self._t[i] = t
        self._aux[i] = aux
        self.cursor = i + 1
        if self.cursor >= self.flush_threshold:
            self.flush()

    def flush(self) -> None:
        n = self.cursor
        if self._flushing or n == 0:
            return
        self._flushing = True
        try:
            # Copy before resetting the cursor so events emitted during
            # on_flush (user-context flushes) don't clobber the batch.
            batch = {
                "kind": self._kind[:n].copy(),
                "region": self._region[:n].copy(),
                "t": self._t[:n].copy(),
                "aux": self._aux[:n].copy(),
            }
            self.cursor = 0
            self.n_flushed += n
            if self.on_flush is not None:
                self.on_flush(self.thread_id, batch)
        finally:
            self._flushing = False


BUFFER_STRATEGIES = {
    "list": ListEventBuffer,
    "numpy": NumpyEventBuffer,
}
