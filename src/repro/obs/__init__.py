"""repro.obs — unified observability: metrics, traces, drift (DESIGN.md §12).

Three dependency-free pieces plus one jax-coupled probe:

* :mod:`~repro.obs.metrics` — thread-safe :class:`MetricsRegistry` of
  labeled Counters / Gauges / Histograms (fixed buckets, interpolated
  p50/p95/p99, injectable clock);
* :mod:`~repro.obs.trace`   — nested :class:`Tracer` spans over synthesis
  Stages A–D and the serving hot path, JSONL-exportable;
* :mod:`~repro.obs.export`  — Prometheus text exposition + JSON snapshot
  + CLI table renderers;
* :mod:`~repro.obs.drift`   — cost-model drift: the planner's roofline
  prediction per dispatch group vs its measured latency (imported lazily:
  it pulls in jax and repro.core, which the pure-telemetry pieces must
  not).
"""
from __future__ import annotations

from .export import (parse_prometheus, render_table, snapshot_document,
                     to_prometheus, write_metrics_json)
from .metrics import (FRACTION_BUCKETS, LATENCY_BUCKETS_S, Counter, Gauge,
                      Histogram, MetricsRegistry, pretouch)
from .trace import Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "pretouch",
    "LATENCY_BUCKETS_S", "FRACTION_BUCKETS",
    "Span", "Tracer",
    "to_prometheus", "parse_prometheus", "render_table",
    "snapshot_document", "write_metrics_json",
    "GroupDrift", "DriftReport", "measure_drift",
]

_LAZY_DRIFT = {"GroupDrift", "DriftReport", "measure_drift"}


def __getattr__(name: str):
    if name in _LAZY_DRIFT:
        from . import drift
        return getattr(drift, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
