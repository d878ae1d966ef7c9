"""Measurement manager — lifecycle, user instrumentation API, buffers.

This is the Python-side equivalent of the Score-P measurement system: it owns
the region registry, the per-thread event buffers, the instrumenter, and the
substrates, and provides the user-instrumentation API (paper: Score-P user
regions):

    import repro.core as rmon
    rmon.init(instrumenter="profile", substrates=("profiling", "tracing"))
    with rmon.region("train_step"):
        ...
    rmon.metric("tokens", 4096)
    rmon.finalize()

All public entry points are safe no-ops when measurement is inactive, so
library code can be annotated unconditionally.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
import warnings
from dataclasses import dataclass, replace
from functools import wraps
from typing import Any, Dict, List, Optional, Tuple

from .buffer import BUFFER_STRATEGIES, EV_ENTER, EV_EXIT
from .filtering import Filter
from .instrumenters import make_instrumenter
from .memsys.poller import GcWatcher
from .memsys.substrate import DEFAULT_PERIOD_S, DEFAULT_TOPN
from .regions import RegionRegistry
from .schema import stamp
from .substrates import make_substrate
from .topology import ENV_PREFIX, ProcessTopology  # noqa: F401  (re-exported)


@dataclass
class MeasurementConfig:
    """Everything one measurement run is parameterized by.

    Round-trips through the process environment (``from_env``/``to_env``,
    ``REPRO_MONITOR_*`` variables) so the two-phase bootstrap and any
    forked worker see an identical configuration; see docs/CLI.md for the
    CLI flags each field maps to and docs/ARTIFACTS.md for the artifacts
    the substrate selection produces.
    """

    instrumenter: str = "profile"
    substrates: Tuple[str, ...] = ("profiling", "tracing", "metrics")
    out_dir: str = "repro-traces"
    run_dir: Optional[str] = None  # explicit run dir (tests); else derived
    filter_spec: str = ""
    flush_threshold: int = 1 << 16
    sampling_period: int = 97
    # Target recorded-pair rate (samples/s) for the "adaptive" instrumenter
    # (PEP 669 epoch sampler, 3.12+); also caps the governor's projected
    # cost for the adaptive ladder rung.
    adaptive_rate: float = 4000.0
    buffer_strategy: str = "list"
    # Memory monitoring (repro.core.memsys): poller period / top-N region
    # table size.  The substrate itself is off unless "memory" appears in
    # ``substrates`` (or REPRO_MONITOR_MEMORY=1 adds it via from_env).
    memory_period: float = DEFAULT_PERIOD_S
    memory_topn: int = DEFAULT_TOPN
    # Overhead budget as fractional dilation (0.05 = 5%); > 0 enables the
    # runtime governor (repro.core.governor), which calibrates per-event
    # cost at startup and escalates (exclude regions -> raise sampling
    # period -> downgrade instrumenter) to keep estimated overhead under
    # budget.  0 disables it.
    budget: float = 0.0
    # ``rank`` is kept as a convenience init arg; ``topology`` is the source
    # of truth (rank + world size + local rank + mesh shape) and the two are
    # synchronized in __post_init__.  ``rank=None`` (the default) means
    # "take it from topology"; an explicit integer — including 0 — wins.
    rank: Optional[int] = None
    topology: Optional[ProcessTopology] = None
    experiment: str = "run"
    chrome_export: bool = True
    keep_series: bool = True
    # Emit the unified HTML report (repro.core.report) into the run dir at
    # finalize.  Off by default: report generation re-reads every artifact
    # just written, which launch scripts may prefer to do offline via
    # ``python -m repro.core.analysis report``.
    report: bool = False
    # Path to a static_plan.json (repro.core.staticpass) produced by
    # ``analysis plan``.  When set, the plan's exclude patterns merge into
    # the filter as runtime excludes (same ``exclude!`` precedence the
    # governor uses) and its predicted offenders warm-start the governor.
    # The plan is copied into the run dir at start() for provenance.
    static_plan: str = ""
    # Live continuous-monitoring agent (repro.agent): publish flush batches
    # into a shared-memory ring; rank 0 additionally runs the sidecar
    # aggregator + HTTP endpoint (/report, /stats.json, /healthz) on
    # ``agent_port`` (0 = ephemeral).
    agent: bool = False
    agent_port: int = 0

    def __post_init__(self):
        if self.topology is None:
            # world size is unknown here; rank+1 is the smallest valid value
            r = self.rank or 0
            self.topology = ProcessTopology(rank=r, world_size=r + 1)
        if self.rank is None:
            self.rank = self.topology.rank
        elif self.topology.rank != self.rank:
            self.topology = self.topology.with_rank(self.rank)

    # -- env round-trip (used by the two-phase bootstrap) -------------------

    @classmethod
    def from_env(cls, environ=os.environ) -> "MeasurementConfig":
        def get(name, default):
            return environ.get(ENV_PREFIX + name, default)

        topology = ProcessTopology.from_env(environ)
        substrates = tuple(
            s.strip()
            for s in get("SUBSTRATES", "profiling,tracing,metrics").split(",")
            if s.strip()
        )
        # REPRO_MONITOR_MEMORY=1 is the one-knob switch for the memory
        # subsystem: it appends the substrate without the user re-listing
        # the default substrate set.
        if get("MEMORY", "0") not in ("0", "false", "") and "memory" not in substrates:
            substrates = substrates + ("memory",)
        return cls(
            instrumenter=get("INSTRUMENTER", cls.instrumenter),
            substrates=substrates,
            out_dir=get("OUT", cls.out_dir),
            run_dir=environ.get(ENV_PREFIX + "RUN_DIR") or None,
            filter_spec=get("FILTER", cls.filter_spec),
            flush_threshold=int(get("FLUSH", cls.flush_threshold)),
            sampling_period=int(get("SAMPLING_PERIOD", cls.sampling_period)),
            adaptive_rate=float(get("ADAPTIVE_RATE", cls.adaptive_rate)),
            buffer_strategy=get("BUFFER", cls.buffer_strategy),
            memory_period=float(get("MEMORY_PERIOD", cls.memory_period)),
            memory_topn=int(get("MEMORY_TOPN", cls.memory_topn)),
            budget=float(get("BUDGET", cls.budget)),
            rank=topology.rank,
            topology=topology,
            experiment=get("EXPERIMENT", cls.experiment),
            chrome_export=get("CHROME", "1") not in ("0", "false", ""),
            keep_series=get("SERIES", "1") not in ("0", "false", ""),
            report=get("REPORT", "0") not in ("0", "false", ""),
            static_plan=get("STATIC_PLAN", cls.static_plan),
            agent=get("AGENT", "0") not in ("0", "false", ""),
            agent_port=int(get("AGENT_PORT", cls.agent_port)),
        )

    def to_env(self) -> Dict[str, str]:
        env = {
            ENV_PREFIX + "INSTRUMENTER": self.instrumenter,
            ENV_PREFIX + "SUBSTRATES": ",".join(self.substrates),
            ENV_PREFIX + "OUT": self.out_dir,
            ENV_PREFIX + "FILTER": self.filter_spec,
            ENV_PREFIX + "FLUSH": str(self.flush_threshold),
            ENV_PREFIX + "SAMPLING_PERIOD": str(self.sampling_period),
            ENV_PREFIX + "ADAPTIVE_RATE": str(self.adaptive_rate),
            ENV_PREFIX + "BUFFER": self.buffer_strategy,
            ENV_PREFIX + "MEMORY": "1" if "memory" in self.substrates else "0",
            ENV_PREFIX + "MEMORY_PERIOD": str(self.memory_period),
            ENV_PREFIX + "MEMORY_TOPN": str(self.memory_topn),
            ENV_PREFIX + "BUDGET": str(self.budget),
            ENV_PREFIX + "EXPERIMENT": self.experiment,
            ENV_PREFIX + "CHROME": "1" if self.chrome_export else "0",
            ENV_PREFIX + "SERIES": "1" if self.keep_series else "0",
            ENV_PREFIX + "REPORT": "1" if self.report else "0",
            ENV_PREFIX + "AGENT": "1" if self.agent else "0",
            ENV_PREFIX + "AGENT_PORT": str(self.agent_port),
        }
        env.update(self.topology.to_env())  # RANK / WORLD_SIZE / LOCAL_RANK / MESH
        if self.run_dir:
            env[ENV_PREFIX + "RUN_DIR"] = self.run_dir
        if self.static_plan:
            env[ENV_PREFIX + "STATIC_PLAN"] = self.static_plan
        return env


class Measurement:
    """One measurement run: regions + buffers + instrumenter + substrates.

    Owns the full lifecycle (``start`` → event recording → ``finalize``)
    and the artifact contract of a run directory.  After ``finalize()``
    the run dir contains, per enabled substrate (see docs/ARTIFACTS.md
    for the field tables; every JSON carries ``report_schema_version``):

    ======================  =====================================================
    artifact                writer / contents
    ======================  =====================================================
    meta.json               always — topology, epochs, event counts
    profile.json (+ .txt)   "profiling" — call tree + flat per-region table
    defs.json + streams     "tracing" — raw event streams + region definitions
    trace.json              "tracing" — Chrome/Perfetto trace (unless disabled)
    metrics.json            "metrics" — metric aggregates + time series
    memory.json             "memory" — per-region allocation attribution,
                            RSS/heap/GC/fd timelines
    governor.json           budget > 0 — calibration, actions, suggested filter
    report.html             ``config.report`` — self-contained HTML report
                            fusing all of the above (repro.core.report)
    ======================  =====================================================

    Thread-safe event intake: each thread appends to its own buffer; flushes
    fan batches out to the substrates under one lock.

    The measurement owns the one GC watcher (the memory substrate reads its
    pauses) and, once ``jax`` is imported, a ``jax_events.JaxBridge``: while
    a profiler session is on, user regions and GC pauses are mirrored into
    the profiler's trace as ``repro/...`` spans, and JAX compile events are
    recorded as ``jax.compile.*`` metrics.
    """

    def __init__(self, config: MeasurementConfig):
        self.config = config
        self.gc = GcWatcher()
        #: repro.core.jax_events.JaxBridge, made at start or on the first
        #: region or flush after ``jax`` is imported; None until then.
        self.jax = None
        self.filter = Filter.from_spec(config.filter_spec)
        self.regions = RegionRegistry(decide=self.filter.decide)
        self._local = threading.local()
        self._buffers: List[Any] = []
        self._buffer_tids: set = set()
        self._buffers_lock = threading.RLock()
        self._flush_lock = threading.RLock()
        self._substrates = []
        for name in config.substrates:
            if name == "tracing":
                self._substrates.append(make_substrate(name, chrome_export=config.chrome_export))
            elif name == "metrics":
                self._substrates.append(make_substrate(name, keep_series=config.keep_series))
            elif name == "memory":
                self._substrates.append(make_substrate(
                    name, period=config.memory_period, topn=config.memory_topn, gc=self.gc))
            else:
                self._substrates.append(make_substrate(name))
        if config.instrumenter == "sampling":
            self.instrumenter = make_instrumenter("sampling", period=config.sampling_period)
        elif config.instrumenter == "adaptive":
            self.instrumenter = make_instrumenter("adaptive", target_rate=config.adaptive_rate)
        else:
            self.instrumenter = make_instrumenter(config.instrumenter)
        if config.budget > 0:
            from .governor import Governor  # late import: governor imports core modules

            self.governor: Optional[Governor] = Governor(self, config.budget)
        else:
            self.governor = None
        #: The loaded static plan dict (repro.core.staticpass), or None.
        #: Set by apply_plan — either here via config.static_plan or later
        #: by a caller holding an already-loaded plan.
        self.static_plan: Optional[Dict[str, Any]] = None
        if config.static_plan:
            from .staticpass import apply_plan, load_plan

            # Before the instrumenter installs: plan excludes must be in the
            # filter before any region verdict is cached.  A bad plan path
            # raises MissingArtifact here, at construction, not mid-run.
            apply_plan(self, load_plan(config.static_plan))
        #: Live-monitoring runtime (repro.agent.runtime.AgentRuntime), or
        #: None.  Created in start() when config.agent is set, or later via
        #: attach_agent(); the flush path fans out to it like a substrate.
        self.agent = None
        self._buffer_cls = BUFFER_STRATEGIES[config.buffer_strategy]
        self.run_dir = config.run_dir or os.path.join(
            config.out_dir,
            f"{config.experiment}-{time.strftime('%Y%m%d-%H%M%S')}"
            f"-p{os.getpid()}-{config.topology.tag()}",
        )
        self.started = False
        self.finalized = False
        self.epoch_time_ns = 0
        self.epoch_perf_ns = 0

    # -- buffers -------------------------------------------------------------

    def thread_buffer(self):
        buf = getattr(self._local, "buf", None)
        if buf is None:
            tid = threading.get_ident()
            with self._buffers_lock:
                # CPython reuses thread idents once a thread exits; each
                # buffer must keep its own event stream (one OTF2 location
                # per thread lifetime), so de-collide reused idents.
                while tid in self._buffer_tids:
                    tid += 1
                self._buffer_tids.add(tid)
                buf = self._buffer_cls(
                    thread_id=tid,
                    flush_threshold=self.config.flush_threshold,
                    on_flush=self._on_flush,
                )
                self._local.buf = buf
                self._buffers.append(buf)
        return buf

    def _on_flush(self, thread_id: int, columns) -> None:
        if self.jax is None and _jax_imported():
            self._bridge_jax()
        with self._flush_lock:
            for sub in self._substrates:
                sub.on_flush(thread_id, columns)
            if self.agent is not None:
                # Before the governor: the governor's very next on_flush
                # pulls this publish's cost (take_publish_cost_ns) into the
                # window it is about to score.
                self.agent.on_flush(thread_id, columns)
            if self.governor is not None:
                # After the substrates: the governor may mutate the filter,
                # the sampling period, or the instrumenter itself, and the
                # batch at hand should be interpreted under the settings it
                # was recorded with.
                self.governor.on_flush(thread_id, columns)

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        if self.started:
            return
        os.makedirs(self.run_dir, exist_ok=True)
        self.epoch_time_ns = time.time_ns()
        self.epoch_perf_ns = time.perf_counter_ns()
        meta = {
            "rank": self.config.rank,
            "topology": self.config.topology.as_dict(),
            "pid": os.getpid(),
            "experiment": self.config.experiment,
            "instrumenter": self.config.instrumenter,
            "substrates": list(self.config.substrates),
            "epoch_time_ns": self.epoch_time_ns,
            "epoch_perf_ns": self.epoch_perf_ns,
        }
        for sub in self._substrates:
            sub.open(self.run_dir, meta)
        if self.static_plan is not None:
            # Provenance copy: the run dir records exactly which plan shaped
            # this run's filter, next to the artifacts it shaped.
            from .staticpass import ARTIFACT as _PLAN_ARTIFACT

            with open(os.path.join(self.run_dir, _PLAN_ARTIFACT), "w") as fh:
                json.dump(self.static_plan, fh, indent=1)
        self.started = True
        self.gc.install()
        if _jax_imported():
            self._bridge_jax()
        if self.config.agent:
            self.attach_agent()
        if self.governor is not None:
            # Calibrate before the instrumenter installs: the probe runs
            # throwaway instrumenter instances on a stub host and must not
            # race the real hook.
            self.governor.calibrate_startup()
        self.instrumenter.install(self)
        if self.governor is not None:
            self.governor.open()

    def _bridge_jax(self) -> None:
        """Make the JAX bridge once ``jax`` is imported, for a live run."""
        with self._buffers_lock:
            if self.jax is not None or not self.started or self.finalized:
                return
            from .jax_events import JaxBridge  # late: imports jax

            self.jax = JaxBridge(self)
            self.gc.span = self.jax.span

    def attach_agent(self, port: Optional[int] = None):
        """Turn on the live-monitoring agent for a started measurement.

        Idempotent: returns the existing runtime if one is live.  Normally
        invoked from :meth:`start` via ``config.agent``; callers that decide
        late (e.g. ``launch serve --agent`` joining an active measurement)
        use this directly."""
        if not self.started or self.finalized:
            raise RuntimeError("attach_agent requires a started measurement")
        if self.agent is not None:
            return self.agent
        if port is not None:
            self.config.agent_port = int(port)
        self.config.agent = True
        from repro.agent.runtime import AgentRuntime  # late: agent imports core

        self.agent = AgentRuntime(self)
        return self.agent

    def stop(self) -> None:
        """Uninstall the instrumenter but keep the run open (re-startable)."""
        if self.started:
            if self.governor is not None:
                # Freeze BEFORE uninstalling: a watchdog tick racing this
                # could otherwise escalate and re-install hooks the user is
                # in the middle of removing.
                self.governor.frozen = True
                self.governor.stop_watchdog()
            self.instrumenter.uninstall()

    def _best_effort(self, label: str, fn, advice: str = "") -> bool:
        """Run one finalize hook in isolation.

        Finalize is a sequence of independent artifact writers; one failing
        hook (a substrate close, the chrome export, the agent shutdown, the
        report) must neither skip the hooks after it nor corrupt the run dir
        — whatever already hit disk stays, whatever comes next still runs.
        Each failure surfaces as a RuntimeWarning naming the hook."""
        try:
            fn()
            return True
        except Exception as exc:
            suffix = f" ({advice})" if advice else ""
            warnings.warn(
                f"{label} failed for {self.run_dir}: {exc!r}{suffix}",
                RuntimeWarning,
            )
            return False

    def finalize(self) -> Optional[str]:
        if not self.started or self.finalized:
            return None
        if self.governor is not None:
            # Freeze BEFORE uninstalling (a racing watchdog tick could
            # swap in fresh hooks on a finalizing measurement) and before
            # draining (the drain flushes partial buffers, which must be
            # accounted without escalating a shutdown).
            self.governor.frozen = True
            self.governor.stop_watchdog()
        self.instrumenter.uninstall()
        self.gc.uninstall()
        self.gc.span = None
        if self.jax is not None:
            self._best_effort("jax bridge close", self.jax.close)
        with self._buffers_lock:
            buffers = list(self._buffers)
        for buf in buffers:
            self._best_effort(f"buffer flush (thread {buf.thread_id})", buf.flush)
        region_table = self.regions.snapshot()
        for sub in self._substrates:
            self._best_effort(
                f"substrate close ({sub.name})",
                lambda s=sub: s.close(region_table),
            )
        if self.governor is not None:
            self._best_effort(
                "governor report", lambda: self.governor.close(self.run_dir)
            )
        for sub in self._substrates:
            # Chrome export runs after *all* substrates closed so the trace
            # can embed metric series (metrics.json) as counter tracks.  An
            # export failure must not abort finalize: the raw artifacts are
            # already on disk and re-exportable offline via to_chrome().
            export_chrome = getattr(sub, "export_chrome", None)
            if export_chrome is not None:
                self._best_effort(
                    f"chrome trace export ({sub.name})",
                    export_chrome,
                    advice="raw streams kept; re-run repro.core.export.export_run",
                )
        if self.agent is not None:
            # After the exports (the last flush above still published), and
            # before meta.json: the ring's writer_closed flag and the final
            # definitions sidecar are part of the run dir contract.
            self._best_effort("agent shutdown", self.agent.close)
        meta = stamp({
            "rank": self.config.rank,
            "topology": self.config.topology.as_dict(),
            "pid": os.getpid(),
            "experiment": self.config.experiment,
            "instrumenter": self.config.instrumenter,
            "buffer_strategy": self.config.buffer_strategy,
            "epoch_time_ns": self.epoch_time_ns,
            "epoch_perf_ns": self.epoch_perf_ns,
            "finalize_time_ns": time.time_ns(),
            "n_regions": len(region_table),
            "events_flushed": sum(getattr(b, "n_flushed", 0) for b in buffers),
        })
        with open(os.path.join(self.run_dir, "meta.json"), "w") as fh:
            json.dump(meta, fh, indent=1)
        self.finalized = True
        if self.config.report:
            # Last: the report generator re-reads every artifact finalized
            # above.  Best-effort for the same reason as the chrome export —
            # raw artifacts are on disk and the report is re-generatable.
            def _report():
                from .report import write_report

                write_report(self.run_dir)

            self._best_effort(
                "report generation",
                _report,
                advice="re-run `python -m repro.core.analysis report`",
            )
        return self.run_dir

    def swap_instrumenter(self, name: str, **kwargs) -> None:
        """Replace the live instrumenter (governor downgrade path).

        Uninstalls the current hook and installs the new one on the calling
        thread (plus threads started afterwards).  Threads that already had
        the old hook lose instrumentation — their stale callbacks self-remove
        via the generation flag; re-hooking a foreign thread's profile slot
        is not possible from here.
        """
        self.instrumenter.uninstall()
        if name == "sampling" and "period" not in kwargs:
            kwargs["period"] = self.config.sampling_period
        elif name == "adaptive" and "target_rate" not in kwargs:
            kwargs["target_rate"] = self.config.adaptive_rate
        self.instrumenter = make_instrumenter(name, **kwargs)
        self.config.instrumenter = name
        if self.started and not self.finalized:
            self.instrumenter.install(self)

    # -- user instrumentation API ---------------------------------------------

    def region(self, name: str, module: str = "user"):
        if self.jax is None and _jax_imported():
            self._bridge_jax()
        rid = self.regions.register_user(name, module)
        return _RegionContext(self, rid)

    def metric(self, name: str, value: float) -> None:
        t = time.perf_counter_ns()
        for sub in self._substrates:
            sub.on_metric(name, float(value), t)
        if self.agent is not None:
            self.agent.on_metric(name, float(value), t)

    def substrate(self, name: str):
        for sub in self._substrates:
            if sub.name == name:
                return sub
        return None


def _jax_imported() -> bool:
    """``jax`` is imported and done importing (a flush under the ``profile``
    instrumenter can run in the middle of ``import jax``)."""
    mod = sys.modules.get("jax")
    return mod is not None and not getattr(getattr(mod, "__spec__", None), "_initializing", False)


class _RegionContext:
    """Reusable enter/exit context for one user region (cheap hot path).

    While a profiler session is on, the region is also a ``repro/<module>/
    <name>`` span in the profiler's trace; with none on, no span is built."""

    __slots__ = ("_m", "_rid", "_span")

    def __init__(self, measurement: Measurement, rid: int):
        self._m = measurement
        self._rid = rid
        self._span = None

    def __enter__(self):
        if self._rid >= 0:
            m = self._m
            bridge = m.jax
            if bridge is not None and bridge.span.is_enabled():
                self._span = bridge.region_span(self._rid)
            buf = m.thread_buffer()
            buf.events.append((EV_ENTER, self._rid, time.perf_counter_ns(), 0))
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._rid >= 0:
            buf = self._m.thread_buffer()
            buf.events.append((EV_EXIT, self._rid, time.perf_counter_ns(), 0))
            if len(buf.events) >= buf.flush_threshold:
                buf.flush()
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None
        return False


class _NullContext:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CONTEXT = _NullContext()

# ----------------------------------------------------------------------------
# Module-level singleton API
# ----------------------------------------------------------------------------

_active: Optional[Measurement] = None
_atexit_registered = False


def init(config: Optional[MeasurementConfig] = None, **overrides) -> Measurement:
    """Initialize and start measurement (idempotent-per-process)."""
    global _active, _atexit_registered
    if _active is not None and not _active.finalized:
        raise RuntimeError("measurement already active; call finalize() first")
    config = replace(config, **overrides) if config else MeasurementConfig(**overrides)
    _active = Measurement(config)
    _active.start()
    if not _atexit_registered:
        atexit.register(finalize)
        _atexit_registered = True
    return _active


def init_from_env() -> Optional[Measurement]:
    """Start measurement if the bootstrap environment is present."""
    if os.environ.get(ENV_PREFIX + "ENABLE") != "1":
        return None
    return init(MeasurementConfig.from_env())


def active() -> Optional[Measurement]:
    """The live :class:`Measurement`, or ``None`` when none is running
    (not initialized, not started, or already finalized).  Library code
    uses this to make instrumentation unconditional-but-free."""
    return _active if (_active is not None and _active.started and not _active.finalized) else None


def region(name: str, module: str = "user"):
    """User-region context manager (paper: ``scorep.user.region``).

    ``with rmon.region("train_step"): ...`` records an enter/exit event
    pair attributed to ``module:name``.  A safe no-op (shared null context)
    when measurement is inactive, so annotations can stay in library code
    permanently.  User regions are never auto-excluded by filters or the
    overhead governor."""
    m = active()
    if m is None:
        return _NULL_CONTEXT
    return m.region(name, module)


def metric(name: str, value: float) -> None:
    """Record one sample of a named metric (paper: Score-P metric plugin
    / user counter).  Lands in metrics.json (aggregates + optional time
    series) and as a Perfetto counter track in trace.json.  No-op when
    measurement is inactive; non-finite values are tolerated (counted,
    serialized as ``null``)."""
    m = active()
    if m is not None:
        m.metric(name, value)


def current_topology() -> ProcessTopology:
    """This process's topology: the active measurement's when one is live,
    otherwise detected from the launcher environment.  Dist modules use this
    to annotate events without reaching into globals."""
    m = _active
    if m is not None:
        return m.config.topology
    return ProcessTopology.from_env()


def instrument(fn=None, *, name: Optional[str] = None, module: str = "user"):
    """Decorator form of :func:`region` (resolves the region per call so the
    decorated function works whether or not measurement is active)."""

    def deco(f):
        region_name = name or getattr(f, "__qualname__", f.__name__)

        @wraps(f)
        def wrapper(*args, **kwargs):
            with region(region_name, module):
                return f(*args, **kwargs)

        return wrapper

    return deco(fn) if fn is not None else deco


def finalize() -> Optional[str]:
    """Finalize the active measurement: uninstall hooks, drain buffers,
    close every substrate (writing their artifacts — see docs/ARTIFACTS.md),
    export the Chrome trace, and return the run directory path (``None``
    when no measurement was active).  Registered via ``atexit`` by
    :func:`init`, so an unexceptional interpreter exit always produces
    complete artifacts."""
    global _active
    m = _active
    if m is None:
        return None
    path = m.finalize()
    _active = None
    return path
