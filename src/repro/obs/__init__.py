"""Observability subsystem (DESIGN.md §12): program spans + metrics.

* ``obs.trace``   — host spans over ``jax.profiler.TraceAnnotation``,
  recorded into the profiler's trace next to the device's ops, and
  ``profile(dir)``, the launchers' ``--trace DIR``.
* ``obs.metrics`` — registry of counters, gauges, and log-bucketed
  histograms with labeled series and JSON snapshots.

``obs.emit`` is the structured line emitter the training loop logs
through.
"""
from .emit import Emitter
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    reset_registry,
)
from .trace import gc_spans, profile, trace_span

__all__ = [
    "Counter",
    "Emitter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "gc_spans",
    "get_registry",
    "profile",
    "reset_registry",
    "trace_span",
]
