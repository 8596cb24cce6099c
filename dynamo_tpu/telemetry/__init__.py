"""Unified engine telemetry: one metrics registry + per-request traces.

The observability layer every component registers into (reference analogs:
lib/llm/src/http/service/metrics.rs for the HTTP instrument set,
ForwardPassMetrics for worker scrapes, and the pipeline Context's stage
list for per-request latency breakdowns). The HTTP frontend renders ONE
Prometheus exposition from a :class:`MetricsRegistry` that the scheduler,
block allocator, KV router, and disagg coordinator all feed; per-request
spans ride :class:`~dynamo_tpu.runtime.engine.AsyncEngineContext` and are
queryable at ``GET /debug/requests/{id}``.
"""

from .flight import (
    CompileTracker,
    FlightRecorder,
    StartupTimeline,
    flight_recorder,
)
from .history import LocalHistorySampler, MetricHistory
from .hub import FleetHub
from .incidents import IncidentConfig, IncidentRecorder
from .registry import (
    DEFAULT_BUCKETS,
    CallbackGauge,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    escape_label_value,
    format_labels,
)
from .tracing import TraceRecorder, span_breakdown
from .watchdog import StallWatchdog, build_flight_artifact

__all__ = [
    "DEFAULT_BUCKETS",
    "CallbackGauge",
    "CompileTracker",
    "Counter",
    "FleetHub",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "IncidentConfig",
    "IncidentRecorder",
    "LocalHistorySampler",
    "MetricHistory",
    "MetricsRegistry",
    "StallWatchdog",
    "StartupTimeline",
    "TraceRecorder",
    "build_flight_artifact",
    "escape_label_value",
    "flight_recorder",
    "format_labels",
    "span_breakdown",
]
