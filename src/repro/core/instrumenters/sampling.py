"""Counting-sampler instrumenter — the paper's future-work item, implemented.

"Further work might include ways to control the runtime overhead […] One
approach could be to sample Python applications." (paper §5)

Design: a call-count sampler on top of ``sys.setprofile``.  Every ``period``-th
*call* event is sampled; a per-thread shadow stack of booleans tracks which
active frames were sampled so their matching *return* is recorded too (a
sampled enter without its exit would corrupt profiles).  Unsampled calls pay
only a closure-local countdown decrement + a list push — no dict lookup, no
modulo, no clock read, no region lookup, no buffer append — so β drops
roughly by the sampling ratio for call-dominated workloads (measured by
``benchmarks/overhead_case2.py``).  ``c_call``-family events carry no frame identity to
balance against and are dispatched out after the two event-name compares —
they never touch the counter or the stack.

The period lives in a shared mutable cell read at every countdown *reset*
(not per event), so the overhead governor can raise it on a live measurement
(``set_period``) and every thread's callback converges within one period.
"""
# repro-lint: allow-file=SP201 — this module IS an instrumenter; installing
# the interpreter hook is its job, not a collision with itself.

from __future__ import annotations

import sys
import threading
import time

from ..buffer import EV_ENTER, EV_EXIT
from .base import Instrumenter


class SamplingInstrumenter(Instrumenter):
    name = "sampling"
    events_supported = ("call", "return")
    # On 3.12+ the next rung down is the PEP 669 adaptive sampler (zero cost
    # for unsampled calls); older interpreters fall straight through to none.
    downgrade_to = "adaptive" if hasattr(sys, "monitoring") else "none"

    def __init__(self, period: int = 97) -> None:
        if period < 1:
            raise ValueError("sampling period must be >= 1")
        self.period = period
        # Shared cell: per-thread callbacks read it on countdown reset, so a
        # live set_period() propagates without rebuilding closures.
        self._period_cell = [period]
        self._measurement = None
        self._installed = False
        # Liveness cell checked by every per-thread closure (see
        # ProfileInstrumenter): uninstall only clears the hook on the calling
        # thread, so stale worker-thread callbacks must self-remove.
        self._active: list = [False]
        self._nfiltered: list = [0]

    def filtered_calls(self) -> int:
        # In sampled calls; ``cost_multiplier`` scales it to hook events.
        return self._nfiltered[0]

    def set_period(self, period: int) -> bool:
        if period < 1:
            raise ValueError("sampling period must be >= 1")
        self.period = period
        self._period_cell[0] = period
        return True

    def cost_multiplier(self) -> float:
        return float(self.period)

    def _make_callback(self, measurement):
        active = self._active
        buf = measurement.thread_buffer()
        append = buf.events.append
        flush = buf.flush
        threshold = buf.flush_threshold
        events = buf.events
        regions = measurement.regions
        by_code = regions.by_code
        register_code = regions.register_code
        clock = time.perf_counter_ns
        period_cell = self._period_cell
        nfiltered = self._nfiltered

        # Per-thread state lives in the closure: a countdown to the next
        # sample (nonlocal int — cheaper than a dict slot + modulo) and the
        # sampled-frame boolean stack.
        remaining = period_cell[0]
        stack = []
        push = stack.append
        pop = stack.pop

        def callback(frame, event, arg):
            nonlocal remaining
            if not active[0]:
                sys.setprofile(None)  # stale generation: self-remove
                return
            if event == "call":
                remaining -= 1
                if remaining:
                    push(False)
                    return
                remaining = period_cell[0]
                code = frame.f_code
                rid = by_code.get(code)
                if rid is None:
                    rid = register_code(code, frame)
                if rid >= 0:
                    append((EV_ENTER, rid, clock(), 0))
                    if len(events) >= threshold:
                        flush()
                    push(True)
                else:
                    # Verdict-miss count (sampled calls only) so the
                    # governor can observe residual hook cost.
                    nfiltered[0] += 1
                    push(False)
            elif event == "return":
                if stack and pop():
                    code = frame.f_code
                    rid = by_code.get(code)
                    if rid is None:
                        rid = register_code(code, frame)
                    if rid >= 0:
                        append((EV_EXIT, rid, clock(), 0))
                        if len(events) >= threshold:
                            flush()
            # c_call / c_return / c_exception: dispatched out above — no
            # counter, no stack, no per-event cost beyond the two compares.

        return callback

    def _thread_entry(self, frame, event, arg):
        if not self._active[0]:
            sys.setprofile(None)
            return None
        callback = self._make_callback(self._measurement)
        sys.setprofile(callback)
        return callback(frame, event, arg)

    def install(self, measurement) -> None:
        self._measurement = measurement
        self._active = [True]
        threading.setprofile(self._thread_entry)
        sys.setprofile(self._make_callback(measurement))
        self._installed = True

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._active[0] = False
        sys.setprofile(None)
        threading.setprofile(None)
        self._installed = False
