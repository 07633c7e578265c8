"""Unified observability layer: metrics registry, structured tracing, and
exporters shared by the engine, I/O scheduler, stores, pool, and pipeline."""
from .registry import (BoundedSeries, Counter, Gauge, Histogram,
                       MetricsRegistry, StatsMap)
from .trace import NULL_SPAN, NullSpan, ProfiledSpan, Span, Tracer
from .export import to_json, to_prometheus

__all__ = [
    "BoundedSeries", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "StatsMap", "NULL_SPAN", "NullSpan", "ProfiledSpan", "Span", "Tracer",
    "to_json", "to_prometheus",
]
