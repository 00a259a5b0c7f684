"""Observability layer: device-side walk tracing, a host-side metrics
registry, and snapshot/report exporters (docs/ARCHITECTURE.md).

Deliberately importable from everywhere — nothing here imports
``repro.core`` or ``repro.launch``, so core kernels, the build drivers and
the serving loop can all report into it without cycles.
"""
from repro.obs.export import (
    load_jsonl,
    render_band_table,
    render_latency_timeline,
    top_band_share,
    write_metrics,
)
from repro.obs.recall import recall_at_k, recall_curve
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    VectorCounter,
    gc_spans,
    get_registry,
    set_registry,
    span,
)
from repro.obs.trace import (
    TraceContext,
    WalkTrace,
    make_trace_context,
    step_of_column,
    walk_trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "TraceContext",
    "VectorCounter",
    "WalkTrace",
    "gc_spans",
    "get_registry",
    "load_jsonl",
    "make_trace_context",
    "recall_at_k",
    "recall_curve",
    "render_band_table",
    "render_latency_timeline",
    "set_registry",
    "span",
    "step_of_column",
    "top_band_share",
    "walk_trace",
    "write_metrics",
]
