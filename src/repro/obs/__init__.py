"""Always-on, clock-injected observability: causal request tracing, a
bounded flight recorder, deterministic exporters, and critical-path
attribution feeding measured costs back into the fusion policy."""
from repro.obs.critical_path import EdgeCostModel, attribute, attribute_trace, build_trees, summarize
from repro.obs.export import (
    chrome_trace,
    dumps_chrome,
    export_all_chrome,
    export_chrome,
    prometheus_text,
    serve_prometheus,
)
from repro.obs.trace import (
    CLOCK_ANCHOR,
    CONTROL_TRACE_ID,
    PHASES,
    FlightRecorder,
    SpanContext,
    SpanRecord,
    Tracer,
    anchor_offset_ns,
    live_tracers,
    null_phase,
    retain_tracers,
)

__all__ = [
    "CLOCK_ANCHOR",
    "CONTROL_TRACE_ID",
    "PHASES",
    "EdgeCostModel",
    "FlightRecorder",
    "SpanContext",
    "SpanRecord",
    "Tracer",
    "anchor_offset_ns",
    "attribute",
    "attribute_trace",
    "build_trees",
    "chrome_trace",
    "dumps_chrome",
    "export_all_chrome",
    "export_chrome",
    "live_tracers",
    "null_phase",
    "prometheus_text",
    "retain_tracers",
    "serve_prometheus",
    "summarize",
]
