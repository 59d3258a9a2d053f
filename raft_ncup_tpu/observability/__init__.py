"""Unified telemetry subsystem: metrics registry, span tracer, export
layer — and the consumer half that closes the loop: health state
machine, SLO burn-rate engine, fault flight recorder
(docs/OBSERVABILITY.md).

One registry, one event stream, every subsystem a producer — serving,
streaming, inference, and the resilience layer all mirror their
accounting here without changing a single legacy ``report()`` key
(``telemetry.LEGACY_KEY_ALIASES`` is the pinned map). The consumers
read it back at runtime: declared SLOs burn against the registry,
paging verdicts flip per-subsystem health READY ⇄ DEGRADED and degrade
the serving tier's anytime iteration budget, and every fault trigger
banks one bounded atomic flight-recorder dump.

Host-only by construction: nothing in this package may import jax,
touch a device array, or add a sync — lint rule JGL010 enforces it
statically, ``telemetry.host_number`` at runtime, and the bench's
telemetry-on-vs-off overhead row measures it.
"""

from raft_ncup_tpu.observability.aggregate import (
    aggregate_registry,
    collect_fleet_records,
    fleet_traces,
    hop_attribution,
    read_jsonl_tolerant,
    render_trace,
)
from raft_ncup_tpu.observability.export import (
    JsonlSink,
    PeriodicSnapshot,
    Telemetry,
    get_telemetry,
    prometheus_text,
    set_telemetry,
    telemetry_report,
    write_healthz,
)
from raft_ncup_tpu.observability.flight import (
    FlightRecorder,
    load_dump,
    match_records,
)
from raft_ncup_tpu.observability.health import (
    DEGRADED,
    DRAINING,
    HALTED,
    READY,
    STARTING,
    STATE_CODES,
    WARMING,
    HealthTracker,
    overall_state,
)
from raft_ncup_tpu.observability.slo import (
    SloEngine,
    SloSpec,
    serve_slos,
    stream_slos,
)
from raft_ncup_tpu.observability.spans import (
    NOOP_SPAN,
    Span,
    SpanTracer,
    TraceContext,
    new_span_id,
    new_trace_id,
)
from raft_ncup_tpu.observability.startup import (
    StartupPhase,
    StartupRecord,
    get_startup_record,
    set_startup_record,
    startup_line,
    startup_report,
)
from raft_ncup_tpu.observability.telemetry import (
    DEFAULT_BUCKETS_MS,
    LEGACY_KEY_ALIASES,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    host_number,
)

__all__ = [
    "Counter",
    "DEFAULT_BUCKETS_MS",
    "DEGRADED",
    "DRAINING",
    "FlightRecorder",
    "Gauge",
    "HALTED",
    "HealthTracker",
    "Histogram",
    "JsonlSink",
    "LEGACY_KEY_ALIASES",
    "MetricsRegistry",
    "NOOP_SPAN",
    "PeriodicSnapshot",
    "READY",
    "STARTING",
    "STATE_CODES",
    "SloEngine",
    "SloSpec",
    "Span",
    "SpanTracer",
    "StartupPhase",
    "StartupRecord",
    "Telemetry",
    "TraceContext",
    "WARMING",
    "aggregate_registry",
    "collect_fleet_records",
    "fleet_traces",
    "get_startup_record",
    "get_telemetry",
    "hop_attribution",
    "host_number",
    "load_dump",
    "match_records",
    "new_span_id",
    "new_trace_id",
    "overall_state",
    "prometheus_text",
    "read_jsonl_tolerant",
    "render_trace",
    "serve_slos",
    "set_startup_record",
    "set_telemetry",
    "startup_line",
    "startup_report",
    "stream_slos",
    "telemetry_report",
    "write_healthz",
]
