"""``sys.monitoring`` instrumenter (PEP 669) — beyond-paper optimization.

The paper (2020) predates CPython 3.12's ``sys.monitoring``, which was built
precisely to lower the cost that the paper measures for ``sys.setprofile``:
callbacks are registered per event kind, receive the code object directly
(no frame materialization on the fast path), and can be disabled per
location.  This instrumenter is the modern re-implementation of the paper's
``profile`` instrumenter; ``benchmarks/overhead_case2.py`` quantifies the β
improvement.

Events observed: PY_START/PY_RETURN (+ PY_UNWIND for exceptional exits and
PY_YIELD/PY_RESUME so generator suspension balances like ``sys.setprofile``'s
call/return semantics).  C-function events are intentionally not subscribed —
subscribing ``CALL`` would reintroduce per-call argument materialization and
most of the cost this instrumenter exists to avoid.

Filtered regions cost zero after the first hit: callbacks return
``sys.monitoring.DISABLE`` for code objects whose filter verdict is
``FILTERED``, so the interpreter stops dispatching that (code, location)
entirely — no callback, no dict lookup, nothing.  The exception is
``PY_UNWIND``, which CPython defines as not locally disableable (returning
DISABLE from it raises ValueError); its callback does the balancing work and
returns None — exceptional exits from filtered code stay a per-event cost,
but they are rare by construction.  DISABLE state lives on the code object
and survives ``free_tool_id``, so ``install`` calls ``restart_events()`` to
clear verdicts left over from a previous measurement (or calibration probe)
in the same process; a registered :meth:`RegionRegistry.add_refilter_hook`
re-arms events whenever the governor tightens the filter on a live
measurement, giving every tool a fresh first hit under the new verdicts.
"""

from __future__ import annotations

import sys
import threading
import time

from ..buffer import EV_ENTER, EV_EXIT
from .base import Instrumenter

_TOOL_NAME = "repro-monitor"


def acquire_tool_id(mon, name: str) -> int:
    """Claim a free PEP 669 tool id, never stealing a foreign tool.

    Prefers ``PROFILER_ID`` (this *is* a profiler), then walks the remaining
    ids 0..5; an id whose ``get_tool`` is non-None belongs to someone else
    (debugger, coverage, another profiler) and is skipped — ``free_tool_id``
    on it would silently unregister that tool.  Raises ``RuntimeError``
    naming the holders when all six ids are taken.
    """
    candidates = [mon.PROFILER_ID] + [i for i in range(6) if i != mon.PROFILER_ID]
    for tool_id in candidates:
        if mon.get_tool(tool_id) is not None:
            continue
        try:
            mon.use_tool_id(tool_id, name)
        except ValueError:  # lost a race for the id; try the next one
            continue
        return tool_id
    holders = ", ".join(
        f"{i}={mon.get_tool(i)!r}" for i in range(6) if mon.get_tool(i) is not None
    )
    raise RuntimeError(
        f"no free sys.monitoring tool id for {name!r} (all in use: {holders})"
    )


class MonitoringInstrumenter(Instrumenter):
    name = "monitoring"
    events_supported = ("call", "return")
    # Governor downgrade rung: exhaustive PEP 669 events -> counting sampler.
    downgrade_to = "sampling"
    # Filtered verdicts cost nothing per call: the callback returns DISABLE
    # on first hit and the interpreter never dispatches that location again.
    zero_cost_filtered = True

    def __init__(self) -> None:
        self._measurement = None
        self._installed = False
        self._tool_id = None
        self._regions = None
        self._nfiltered: list = [0]

    def filtered_calls(self) -> int:
        return self._nfiltered[0]

    def _make_callbacks(self, measurement):
        mon = sys.monitoring
        DISABLE = mon.DISABLE
        regions = measurement.regions
        by_code = regions.by_code
        register_code = regions.register_code
        clock = time.perf_counter_ns
        get_ident = threading.get_ident
        # thread ident -> bound append of that thread's buffer
        appends = {}
        buffers = {}

        def _bind(ident):
            buf = measurement.thread_buffer()
            buffers[ident] = buf
            appends[ident] = buf.events.append
            return appends[ident]

        def _maybe_flush(ident):
            buf = buffers[ident]
            if len(buf.events) >= buf.flush_threshold:
                buf.flush()
                appends[ident] = buf.events.append

        nfiltered = self._nfiltered

        def on_start(code, instruction_offset):
            t = clock()
            rid = by_code.get(code)
            if rid is None:
                rid = register_code(code, None)
            if rid >= 0:
                ident = get_ident()
                append = appends.get(ident)
                if append is None:
                    append = _bind(ident)
                append((EV_ENTER, rid, t, 0))
                _maybe_flush(ident)
                return None
            # Verdict-miss count for the governor's residual-cost
            # observation: at most one per (code, location) per
            # restart_events epoch — DISABLE retires the location.
            nfiltered[0] += 1
            return DISABLE

        def on_return(code, instruction_offset, retval):
            t = clock()
            rid = by_code.get(code)
            if rid is None:
                rid = register_code(code, None)
            if rid >= 0:
                ident = get_ident()
                append = appends.get(ident)
                if append is None:
                    append = _bind(ident)
                append((EV_EXIT, rid, t, 0))
                _maybe_flush(ident)
                return None
            return DISABLE

        def on_unwind(code, instruction_offset, exception):
            # PY_UNWIND is not locally disableable (returning DISABLE raises
            # ValueError), so exceptional exits always pay the callback; the
            # filtered path just declines to record.
            rid = by_code.get(code)
            if rid is None:
                rid = register_code(code, None)
            if rid >= 0:
                t = clock()
                ident = get_ident()
                append = appends.get(ident)
                if append is None:
                    append = _bind(ident)
                append((EV_EXIT, rid, t, 0))
                _maybe_flush(ident)

        return on_start, on_return, on_unwind

    def _rearm(self) -> None:
        """Refilter hook: re-enable every DISABLEd location so tightened
        verdicts get their one fresh hit (and then go dark again)."""
        if self._installed:
            sys.monitoring.restart_events()

    def install(self, measurement) -> None:
        mon = sys.monitoring
        tool_id = acquire_tool_id(mon, _TOOL_NAME)
        self._tool_id = tool_id
        self._measurement = measurement
        self._regions = measurement.regions
        on_start, on_return, on_unwind = self._make_callbacks(measurement)
        ev = mon.events
        mon.register_callback(tool_id, ev.PY_START, on_start)
        mon.register_callback(tool_id, ev.PY_RESUME, on_start)
        mon.register_callback(tool_id, ev.PY_RETURN, on_return)
        mon.register_callback(tool_id, ev.PY_YIELD, on_return)
        mon.register_callback(tool_id, ev.PY_UNWIND, on_unwind)
        mon.set_events(
            tool_id, ev.PY_START | ev.PY_RESUME | ev.PY_RETURN | ev.PY_YIELD | ev.PY_UNWIND
        )
        # DISABLE state is per (code, location) and survives tool-id reuse:
        # a previous measurement (or the calibration probe) in this process
        # may have retired locations this measurement must observe.
        mon.restart_events()
        self._regions.add_refilter_hook(self._rearm)
        self._installed = True

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._installed = False
        if self._regions is not None:
            self._regions.remove_refilter_hook(self._rearm)
            self._regions = None
        mon = sys.monitoring
        ev = mon.events
        mon.set_events(self._tool_id, 0)
        for kind in (ev.PY_START, ev.PY_RESUME, ev.PY_RETURN, ev.PY_YIELD, ev.PY_UNWIND):
            mon.register_callback(self._tool_id, kind, None)
        mon.free_tool_id(self._tool_id)
        self._tool_id = None
