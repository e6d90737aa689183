"""The reader of the program's flusher counter: ``flush_lag_ms`` on a
synthetic ``ctx``, and on the counters of a program that has no such
counter (it finds nothing)."""

from types import SimpleNamespace

import pytest

from bench_tiny import ROOT
from bench import harness

NEW = ("flush_lag_ms",)


def _ctx(**counters):
    return SimpleNamespace(counters=counters)


def _read(name, ctx):
    return harness.load_reader(ROOT, name).read(ctx)


def test_counter_readers():
    ctx = _ctx(probes_fired=4, flush_lag_us=2000)
    assert _read("flush_lag_ms", ctx) == pytest.approx(0.5)
    # a flusher that took every batch at once reads 0, not nothing
    assert _read("flush_lag_ms", _ctx(probes_fired=4, flush_lag_us=0)) == 0
    # a program without the counter, or a window without a probe
    assert _read("flush_lag_ms", _ctx(probes_fired=4)) is None
    assert _read("flush_lag_ms", _ctx(flush_lag_us=0)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_without_spans(name):
    """The counters of a program that predates the counter, as
    ``Cell.counters`` copies them, read None: never 0, never an error."""
    counters = {"requests": 80, "probes_fired": 12, "predicates_probed": 50,
                "rows_scanned": 10, "rows_full_equiv": 20}
    assert _read(name, _ctx(**counters)) is None

