"""Telemetry subsystem: metrics registry, trace spans, q-error accounting.

The serving stack's sensor layer (docs/observability.md):

  * ``MetricsRegistry`` — thread-safe counters / gauges / exact-
    percentile histograms; every subsystem's counters live here (one
    source of truth for ``stats()``, the exit summary, and
    ``--metrics-json``).
  * ``Tracer`` — sampled JSONL per-request trace spans
    (``serve --trace-out PATH --trace-sample N``).
  * ``spans`` — profiler spans at the layer boundaries, on the device
    trace's clock (``serve --profile-dir``); always on, under a
    microsecond each outside a profiler session.
  * ``ObsHub`` — the single handle (registry + tracer) threaded through
    coalescer / serve / chaos / index / plan execution.
  * ``report`` — the canonical snapshot schema and the unified exit
    renderer.

Telemetry observes host-side only — it never touches probe inputs,
shapes, or device buffers, so probe results are bitwise identical with
telemetry on or off (guarded by tests/test_observability.py).
"""

from repro.obs.hub import ObsHub
from repro.obs.registry import (
    LATENCY_MS_EDGES,
    QERROR_EDGES,
    SECONDS_EDGES,
    UNIT_EDGES,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import Tracer, get_flush_ctx, set_flush_ctx

__all__ = [
    "ObsHub", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "Tracer", "get_flush_ctx", "set_flush_ctx",
    "LATENCY_MS_EDGES", "QERROR_EDGES", "SECONDS_EDGES", "UNIT_EDGES",
]
