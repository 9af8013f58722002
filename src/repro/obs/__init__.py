"""Telemetry core (DESIGN.md section 13): unified metrics registry,
merge-pipeline trace spans, and the retrace/recompile watchdog.

This is the instrumentation contract everything reports through:
engines carry a `Telemetry`, the facade times ops into it,
`OnlineIndex`/the engines trace their merge pipelines with the fixed
`MERGE_SPANS` taxonomy, and `benchmarks/run.py --metrics-json` exports
`LearnedIndex.metrics()` snapshots per workload section.
"""

from .metrics import (LatencyHistogram, MetricsRegistry, PERCENTILES,
                      latency_summary)
from .telemetry import NULL_TELEMETRY, OPS, SCHEMA_VERSION, Telemetry
from .trace_export import (TRACE_SCHEMA_VERSION, TraceBuffer,
                           current_trace_ids, mint_trace_id, trace_context)
from .tracing import (ENGINE_SPANS, GC_SPAN, MERGE_SPANS, RECOVERY_SPANS,
                      SERVE_SPANS, Span, SpanRecorder)
from .inspect import INSPECT_SCHEMA_VERSION, build_inspect
from . import watchdog

__all__ = [
    "LatencyHistogram", "MetricsRegistry", "PERCENTILES", "latency_summary",
    "NULL_TELEMETRY", "OPS", "SCHEMA_VERSION", "Telemetry",
    "TRACE_SCHEMA_VERSION", "TraceBuffer", "current_trace_ids",
    "mint_trace_id", "trace_context",
    "ENGINE_SPANS", "GC_SPAN", "MERGE_SPANS", "RECOVERY_SPANS",
    "SERVE_SPANS", "Span", "SpanRecorder",
    "INSPECT_SCHEMA_VERSION", "build_inspect",
    "watchdog",
]
