"""Program spans on the profiler's clock.

``span(name, **ids)`` opens a ``jax.profiler.TraceAnnotation``. Inside a
profiler session (``jax.profiler.trace``, ``serve --profile-dir``, the
benchmark's traced run) the span lands in the same ``.xplane.pb`` as the
device's ops, on one clock, with ``ids`` as the event's stats; outside
one it costs under a microsecond. There is no switch: spans open once
per call at a layer boundary, never once per predicate: six per plan
and eight per flush.

The names below are constants that call sites, the benchmark's readers
(``bench/metrics/``) and the span table of docs/observability.md share:
per span, its thread, the boundary it marks and its stats.

This module imports only jax, so ``core/`` and ``index/`` may use it
(the one exception to the layering ``obs/hub.py`` states).
"""

from __future__ import annotations

import contextlib
import itertools
import threading

import jax

__all__ = ["SPANS", "span", "plan_span", "current_plan"]

# client threads
PLAN = "plan"                           # plan_query (plan, filters)
PLAN_EMBED = "plan.embed"               # predicate text embeddings
PLAN_SPECIFICITY = "plan.specificity"   # MLP apply + copy back
PLAN_KVBATCH = "plan.kvbatch"           # kv-batch threshold calibration
PLAN_PROBE = "plan.probe"               # the estimator's probe call
COALESCER_SUBMIT = "coalescer.submit"   # probe_outcomes (plan, if bound)
# the coalescer's flusher thread
COALESCER_AWAIT_BATCH = "coalescer.await_batch"   # a window to close
COALESCER_FLUSH = "coalescer.flush"     # one flush (flush, batch, bucket)
HIST_PROBE = "hist.probe"               # the histogram probe's dispatch
HIST_COPY_BACK = "hist.copy_back"       # counts and top-k to the host
COALESCER_SCATTER = "coalescer.scatter"   # cache fill, waking waiters
INDEX_PLAN_SCAN = "index.plan_scan"     # host classification of clusters
INDEX_GATHER = "index.gather"           # scan rows, gather dispatch
INDEX_SCAN = "index.scan"               # masked scan dispatch

SPANS = (PLAN, PLAN_EMBED, PLAN_SPECIFICITY, PLAN_KVBATCH, PLAN_PROBE,
         COALESCER_SUBMIT, COALESCER_AWAIT_BATCH, COALESCER_FLUSH,
         HIST_PROBE, HIST_COPY_BACK, COALESCER_SCATTER, INDEX_PLAN_SCAN,
         INDEX_GATHER, INDEX_SCAN)

_plan_ids = itertools.count(1)
_local = threading.local()


def span(name: str, **ids):
    """A profiler span named ``name``; ``ids`` become its stats."""
    return jax.profiler.TraceAnnotation(name, **ids)


@contextlib.contextmanager
def plan_span(filters: int):
    """The ``plan`` span, with a process-unique plan id bound on this
    thread for its length, so spans further down (``coalescer.submit``)
    can name the plan they serve."""
    pid = next(_plan_ids)
    _local.plan = pid
    try:
        with span(PLAN, plan=pid, filters=int(filters)):
            yield pid
    finally:
        _local.plan = None


def current_plan() -> int | None:
    """The plan id bound on this thread, or None outside a plan."""
    return getattr(_local, "plan", None)
