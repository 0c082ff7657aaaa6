"""``fedml_tpu.core.obs`` — the round-trace observability layer.

One process-global context (configured by ``core.mlops.init`` when
``args.obs_trace`` is set, torn down by ``mlops.finish``) exposing:

* a :class:`~.trace.Tracer` whose deterministic span ids and W3C-style
  ``traceparent`` header turn each federated round into one cross-process
  span tree (``round → select → invite → client.train → upload →
  journal.append → aggregate → broadcast``, with fault/recovery events
  attached — catalogue in ``docs/OBSERVABILITY.md``);
* a :class:`~.metrics.MetricsRegistry` every library counter mirrors into
  (``tools/lint_obs.py`` forbids NEW bare counter bags outside this
  package and ``core/mlops``);
* module-level helpers (``span`` / ``span_event`` / ``inject`` /
  ``extract`` / ``counter_inc`` / ...) that are cheap no-ops until
  :func:`configure` runs — library code calls them unconditionally, and
  with ``obs_trace`` off the message flow stays bit-identical (no
  traceparent param is ever added).

Everything here is telemetry: emission failures are swallowed, ids carry
no wall-clock, and nothing round-critical may ever depend on a span.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

from .exposition import MetricsExporter
from .flight import DEFAULT_FLIGHT_CAPACITY, FlightRecorder
from .health import (
    DEFAULT_EWMA_ALPHA,
    DEFAULT_WATCHDOG_DEADLINE_S,
    DEFAULT_Z_THRESHOLD,
    HEALTH_STATUS_GAUGE,
    NULL_SILENCE,
    NULL_WATCHDOG,
    HealthPlane,
    SilenceMonitor,
    Watchdog,
)
from .metrics import DEFAULT_TIME_BUCKETS, MetricsRegistry
from .scopes import program_scopes, scope_seconds
from .telemetry import (
    DEFAULT_FLUSH_S,
    DEFAULT_RING_CAPACITY,
    TOPIC_TELEMETRY,
    ClientTelemetry,
    TelemetryMerger,
)
from .trace import (
    NULL_SPAN,
    Span,
    SpanContext,
    Tracer,
    active_ctx,
    round_root_ctx,
    span_id_for,
    trace_id_for,
)

__all__ = [
    "MetricsRegistry", "Tracer", "Span", "SpanContext", "NULL_SPAN",
    "DEFAULT_TIME_BUCKETS", "trace_id_for", "span_id_for", "round_root_ctx",
    "active_ctx", "FlightRecorder", "MetricsExporter",
    "configure", "shutdown", "enabled", "tracer", "registry", "run_id",
    "span", "round_span", "unique_span", "span_event",
    "inject", "extract", "counter_inc", "gauge_set", "histogram_observe",
    "maybe_export_metrics", "slow_round_factor",
    "flight_recorder", "flight_dump", "exporter",
    "sample_resource_gauges", "compile_seconds_total",
    "trace_seconds_total", "compiles_total",
    "program_scopes", "scope_seconds",
    "ClientTelemetry", "TelemetryMerger", "TOPIC_TELEMETRY",
    "telemetry_enabled", "telemetry_flush_s",
    "make_client_telemetry", "make_telemetry_merger",
    "HealthPlane", "Watchdog", "SilenceMonitor",
    "NULL_WATCHDOG", "NULL_SILENCE", "HEALTH_STATUS_GAUGE",
    "health_plane", "health_enabled", "health_watchdog", "health_silence",
    "health_observe", "health_tick", "health_status",
]

_lock = threading.Lock()
_ctx: Dict[str, Any] = {"enabled": False}

# the registry outlives configure/shutdown cycles within a process run so
# counters survive mlops re-init (tests reset it explicitly)
_registry = MetricsRegistry()


def _tapped_emit(flight: FlightRecorder,
                 emit: Callable[[str, Dict[str, Any]], None]):
    """Wrap the sink emit so every record also lands in the flight ring,
    and trigger events (``server_kill`` / ``server_restore`` /
    ``slow_round``) dump the ring AFTER the record is forwarded — the
    trigger itself is the dump's last line."""
    def tapped(topic: str, rec: Dict[str, Any]) -> None:
        try:
            reason = flight.record(topic, rec)
        except Exception:  # recorder trouble must never block the sink
            reason = None
        emit(topic, rec)
        if reason is not None:
            try:
                flight.dump(reason)
            except Exception:
                pass
    return tapped


def _health_event_emitter(name: str, attrs: Dict[str, Any]) -> None:
    """The health plane's event sink: a span event anchored on the last
    round the emit stream saw, so dumps and reports land inside the round
    tree the incident belongs to."""
    t = _ctx.get("tracer")
    if t is None:
        return
    plane = _ctx.get("health")
    ridx = int(getattr(plane, "last_round_idx", 0) or 0) if plane else 0
    try:
        t.span_event(name, None, round_idx=ridx, **attrs)
    except Exception:  # telemetry never raises into the round path
        pass


def configure(args: Any, emit: Callable[[str, Dict[str, Any]], None]) -> None:
    """Enable tracing for this process.  ``emit`` is sink-shaped
    (``(topic, record)``) — ``mlops.init`` passes its fan's emit."""
    run = str(getattr(args, "run_id", "0"))
    health_obj: Optional[HealthPlane] = None
    if bool(int(getattr(args, "obs_health", 0) or 0)):
        try:
            health_obj = HealthPlane(
                registry=_registry,
                clock=getattr(args, "obs_health_clock", None),
                z_threshold=float(
                    getattr(args, "obs_health_z", DEFAULT_Z_THRESHOLD)
                    or DEFAULT_Z_THRESHOLD),
                ewma_alpha=float(
                    getattr(args, "obs_health_ewma_alpha", DEFAULT_EWMA_ALPHA)
                    or DEFAULT_EWMA_ALPHA),
                watchdog_deadline_s=float(
                    getattr(args, "obs_health_watchdog_s",
                            DEFAULT_WATCHDOG_DEADLINE_S)
                    or DEFAULT_WATCHDOG_DEADLINE_S),
                warmup=int(getattr(args, "obs_health_warmup", 8) or 8))
            # health tap wrapped FIRST so the flight tap stays outermost:
            # flight records (and dump-triggers on) every record,
            # including the plane's own events
            emit = health_obj.tap(emit)
        except Exception:  # health misconfig must not take the run down
            health_obj = None
    flight: Optional[FlightRecorder] = None
    cap = int(getattr(args, "obs_flight_capacity", DEFAULT_FLIGHT_CAPACITY)
              or 0)
    if cap > 0:
        flight = FlightRecorder(
            capacity=cap,
            directory=getattr(args, "obs_flight_dir", None) or None,
            run_id=run)
        emit = _tapped_emit(flight, emit)
        if health_obj is not None:
            plane = health_obj
            flight.add_meta_provider(
                lambda: {"health": plane.snapshot_compact()})
    exporter_obj: Optional[MetricsExporter] = None
    port = getattr(args, "obs_export_port", None)
    path = getattr(args, "obs_export_path", None) or None
    port = int(port) if port not in (None, "") else 0
    if port > 0 or path:
        try:
            exporter_obj = MetricsExporter(
                _registry, port=port if port > 0 else None,
                snapshot_path=path,
                health_provider=(health_obj.snapshot
                                 if health_obj is not None else None),
            ).start()
        except Exception:  # a taken port must not take the run down
            exporter_obj = None
    if (health_obj is not None and exporter_obj is not None
            and exporter_obj.serve_thread is not None):
        health_obj.register("obs.exporter",
                            thread=exporter_obj.serve_thread)
    with _lock:
        _ctx.update(
            health=health_obj,
            enabled=True,
            run_id=run,
            emit=emit,
            tracer=Tracer(run, emit),
            export_interval_s=float(
                getattr(args, "obs_metrics_export_interval", 0) or 0),
            slow_round_factor=float(
                getattr(args, "obs_slow_round_factor", 2.0) or 2.0),
            flight=flight,
            exporter=exporter_obj,
            telemetry=bool(int(getattr(args, "obs_telemetry", 0) or 0)),
            telemetry_ring=int(
                getattr(args, "obs_telemetry_ring", DEFAULT_RING_CAPACITY)
                or DEFAULT_RING_CAPACITY),
            telemetry_flush_s=float(
                getattr(args, "obs_telemetry_flush_s", DEFAULT_FLUSH_S)
                or DEFAULT_FLUSH_S),
        )
    if health_obj is not None:
        health_obj.emitter = _health_event_emitter
    _register_compile_listener()


def shutdown() -> None:
    """Final metrics flush + exporter/recorder teardown (idempotent)."""
    with _lock:
        emit = _ctx.get("emit")
        if emit is not None:
            sample_resource_gauges()
            _registry.export_to(emit)
        exporter_obj = _ctx.get("exporter")
        _ctx.clear()
        _ctx["enabled"] = False
    if exporter_obj is not None:
        try:  # joins the serve thread — outside the facade lock
            exporter_obj.shutdown()
        except Exception:
            pass


def enabled() -> bool:
    return bool(_ctx.get("enabled"))


def tracer() -> Optional[Tracer]:
    return _ctx.get("tracer")


def registry() -> MetricsRegistry:
    return _registry


def run_id() -> str:
    return str(_ctx.get("run_id", "0"))


def slow_round_factor() -> float:
    return float(_ctx.get("slow_round_factor", 2.0))


def flight_recorder() -> Optional[FlightRecorder]:
    return _ctx.get("flight")


def flight_dump(reason: str) -> Optional[str]:
    """Dump the flight ring now (server managers call this on unhandled
    handler exceptions); returns the dump path or None."""
    flight = _ctx.get("flight")
    if flight is None:
        return None
    try:
        return flight.dump(reason)
    except Exception:  # telemetry never raises into the round path
        return None


def exporter() -> Optional[MetricsExporter]:
    return _ctx.get("exporter")


# -- live health & SLO plane -------------------------------------------------

def health_plane() -> Optional[HealthPlane]:
    return _ctx.get("health")


def health_enabled() -> bool:
    return _ctx.get("health") is not None


def health_status() -> str:
    plane = _ctx.get("health")
    return plane.status if plane is not None else "ok"


def health_watchdog(name: str, deadline_s: Optional[float] = None,
                    thread: Any = None):
    """Register a named liveness watchdog for a long-lived worker; returns
    a handle whose ``beat`` / ``idle`` / ``close`` are no-ops when the
    health plane is off, so worker loops call them unconditionally."""
    plane = _ctx.get("health")
    if plane is None:
        return NULL_WATCHDOG
    try:
        return plane.register(name, deadline_s=deadline_s, thread=thread)
    except Exception:
        return NULL_WATCHDOG


def health_silence(series: str, max_age_s: Optional[float] = None):
    """The silence monitor for an expected activity stream (chunk acks,
    edge forwards); ``note()`` marks activity, a tick finds the stall."""
    plane = _ctx.get("health")
    if plane is None:
        return NULL_SILENCE
    try:
        return plane.silence(series, max_age_s=max_age_s)
    except Exception:
        return NULL_SILENCE


def health_observe(series: str, value: float) -> None:
    """Push one sample into a rolling SLO window (no-op with health off)."""
    plane = _ctx.get("health")
    if plane is not None:
        try:
            plane.observe(series, value)
        except Exception:
            pass


def health_tick() -> Optional[str]:
    """Run the health checks now; returns the status, or None when the
    plane is off.  Round-close paths get this for free via
    :func:`maybe_export_metrics`."""
    plane = _ctx.get("health")
    if plane is None:
        return None
    try:
        return plane.tick()
    except Exception:
        return None


# -- cross-host telemetry plane ---------------------------------------------

def telemetry_enabled() -> bool:
    return bool(_ctx.get("telemetry"))


def telemetry_flush_s() -> float:
    return float(_ctx.get("telemetry_flush_s", DEFAULT_FLUSH_S))


def make_client_telemetry(node: Any) -> Optional[ClientTelemetry]:
    """A per-manager telemetry capture ring, or None with the plane off.
    Per-instance on purpose: the in-process test harness runs every node
    of a deployment in one interpreter, where a process-global buffer
    would interleave nodes' sequence spaces."""
    if not _ctx.get("telemetry"):
        return None
    return ClientTelemetry(
        node, _ctx.get("run_id", "0"),
        capacity=int(_ctx.get("telemetry_ring", DEFAULT_RING_CAPACITY)))


def make_telemetry_merger() -> Optional[TelemetryMerger]:
    """A per-manager blob merger bound to the configured sink fan and the
    process registry, or None with the plane off."""
    if not _ctx.get("telemetry"):
        return None
    return TelemetryMerger(emit=_ctx.get("emit"), registry=_registry)


# -- resource attribution ---------------------------------------------------

def sample_resource_gauges() -> None:
    """Host memory gauges: current RSS (``/proc/self/statm``) and peak RSS
    (``getrusage``).  Called from every ``maybe_export_metrics`` site, so
    the round-close paths of both managers and both simulators sample it
    for free.  Best-effort on non-Linux."""
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        # ru_maxrss is KiB on Linux
        _registry.gauge_set("proc.max_rss_bytes", float(ru.ru_maxrss) * 1024.0)
    except Exception:
        pass
    try:
        with open("/proc/self/statm", "rb") as f:
            rss_pages = int(f.read().split()[1])
        _registry.gauge_set(
            "proc.rss_bytes", float(rss_pages * os.sysconf("SC_PAGE_SIZE")))
    except (OSError, ValueError, IndexError):
        pass


# XLA start-up accounting: jax.monitoring fires one duration event per
# jaxpr trace, per lowering to MLIR and per backend compile in the process
# (round fns, eval fns, the agg plane) and one plain event per persistent
# compilation-cache hit or write, so two listeners give the compile side of
# the compile-vs-execute split, and what a start pays before its first
# round, without touching any hot path.  Registered once per process; they
# read the live _ctx per event.
_BACKEND_COMPILE = "backend_compile_duration"
_TRACE_EVENTS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration")
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "xla.cache_hits",
                 "/jax/compilation_cache/cache_misses": "xla.cache_misses"}
_compile_state = {"lock": threading.Lock(), "total": 0.0, "trace": 0.0,
                  "requests": 0, "cache_hits": 0, "registered": False}


def _on_jax_event_duration(event: str, duration: float, **kw: Any) -> None:
    if not _ctx.get("enabled"):
        return
    event = str(event)
    if event.endswith(_TRACE_EVENTS):
        with _compile_state["lock"]:
            _compile_state["trace"] += float(duration)
        return
    if not event.endswith(_BACKEND_COMPILE):
        return
    with _compile_state["lock"]:
        _compile_state["total"] += float(duration)
        _compile_state["requests"] += 1
    try:
        _registry.histogram_observe("xla.compile_seconds", float(duration))
    except Exception:
        pass


def _on_jax_event(event: str, **kw: Any) -> None:
    name = _CACHE_EVENTS.get(str(event))
    if name is None or not _ctx.get("enabled"):
        return
    if name == "xla.cache_hits":
        with _compile_state["lock"]:
            _compile_state["cache_hits"] += 1
    _registry.counter_inc(name)


def _register_compile_listener() -> None:
    if _compile_state["registered"]:
        return
    try:
        from jax import monitoring as _monitoring

        _monitoring.register_event_duration_secs_listener(
            _on_jax_event_duration)
        _monitoring.register_event_listener(_on_jax_event)
        _compile_state["registered"] = True
    except Exception:  # jax absent or API moved: attribution degrades
        pass


def compile_seconds_total() -> float:
    """Cumulative XLA backend-compile seconds observed so far; snapshot
    before/after a round call and the difference is that round's compile
    share.  A program loaded from the persistent cache counts its load."""
    with _compile_state["lock"]:
        return float(_compile_state["total"])


def trace_seconds_total() -> float:
    """Cumulative seconds jax spent tracing Python to jaxprs and lowering
    them to MLIR: what a start pays again for every program even when the
    persistent cache saves its compile."""
    with _compile_state["lock"]:
        return float(_compile_state["trace"])


def compiles_total() -> int:
    """How many programs the XLA backend compiled so far: the compile
    requests less those the persistent cache served (``xla.cache_hits``).
    With a warm cache that leaves the programs under jax's cache
    thresholds, which compile on every start."""
    with _compile_state["lock"]:
        return int(_compile_state["requests"] - _compile_state["cache_hits"])


# -- span helpers (no-ops until configure) ----------------------------------

def round_span(round_idx: int, node: Any = 0, annotate: bool = False,
               **attrs: Any):
    t = _ctx.get("tracer")
    if t is None:
        return NULL_SPAN
    return t.round_span(int(round_idx), node=node, annotate=annotate, **attrs)


def span(name: str, parent: Optional[SpanContext] = None,
         round_idx: Optional[int] = None, node: Any = 0, seq: int = 0,
         annotate: bool = False, **attrs: Any):
    t = _ctx.get("tracer")
    if t is None:
        return NULL_SPAN
    return t.span(name, parent, round_idx=round_idx, node=node, seq=seq,
                  annotate=annotate, **attrs)


def unique_span(name: str, parent: Optional[SpanContext] = None,
                round_idx: Optional[int] = None, node: Any = 0,
                annotate: bool = False, **attrs: Any):
    t = _ctx.get("tracer")
    if t is None:
        return NULL_SPAN
    return t.unique_span(name, parent, round_idx=round_idx, node=node,
                         annotate=annotate, **attrs)


def span_event(name: str, ctx: Optional[SpanContext] = None,
               round_idx: Optional[int] = None, node: Any = 0,
               **attrs: Any) -> None:
    t = _ctx.get("tracer")
    if t is not None:
        t.span_event(name, ctx, round_idx=round_idx, node=node, **attrs)


# -- context propagation ----------------------------------------------------

def inject(message: Any, ctx: Optional[SpanContext]) -> None:
    """Stamp ``ctx`` into a :class:`Message`'s params as a ``traceparent``
    string (survives every backend: JSON keeps strings, binary transports
    pickle the whole dict).  No-op when tracing is off or ctx is None, so
    the disabled wire is byte-identical to the pre-obs wire."""
    if ctx is None or not enabled():
        return
    from ..distributed.communication.message import Message

    message.add_params(Message.MSG_ARG_KEY_TRACEPARENT, ctx.to_traceparent())


def extract(message: Any) -> Optional[SpanContext]:
    """The :class:`SpanContext` a peer injected, or None (legacy peer,
    tracing off at the sender, malformed header)."""
    from ..distributed.communication.message import Message

    return SpanContext.from_traceparent(
        message.get(Message.MSG_ARG_KEY_TRACEPARENT))


# -- metrics helpers --------------------------------------------------------

def counter_inc(name: str, n: float = 1,
                labels: Optional[Dict[str, Any]] = None) -> None:
    _registry.counter_inc(name, n, labels)


def gauge_set(name: str, value: float,
              labels: Optional[Dict[str, Any]] = None) -> None:
    _registry.gauge_set(name, value, labels)


def histogram_observe(name: str, value: float,
                      labels: Optional[Dict[str, Any]] = None,
                      buckets=None) -> None:
    _registry.histogram_observe(name, value, labels, buckets)


def maybe_export_metrics() -> bool:
    """Rate-limited registry flush to the sink (round-close call sites);
    obeys ``obs_metrics_export_interval`` (0 = only the shutdown flush).
    Also samples the host resource gauges and, when a sink flush fires,
    refreshes the exporter's file snapshot."""
    emit = _ctx.get("emit")
    if emit is None:
        return False
    sample_resource_gauges()
    health_tick()
    did = _registry.maybe_export(emit, float(_ctx.get("export_interval_s", 0)))
    if did:
        exporter_obj = _ctx.get("exporter")
        if exporter_obj is not None:
            try:
                exporter_obj.snapshot()
            except OSError:
                pass
    return did
