"""The trace reduction, on a small recorded trace.

The fixture is 400 ms of the profiler trace of one chip run of
``ecommerce-1m-k1024.leaf-c2`` on a TPU v5e: the device's "XLA Ops" and
"XLA Modules" lines and the host spans of 20 us or more, with the window
span set to that stretch.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import ROOT
from bench import devtrace, harness

FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e_leaf_c2.json"


@pytest.fixture(scope="module")
def planes():
    return json.loads(FIXTURE.read_text())


@pytest.fixture(scope="module")
def kernels():
    return harness.load_reader(ROOT, "kernel_ms").KERNELS


def _ops(planes):
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    return next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")["events"]


def test_busy_time_is_the_union_of_op_intervals(planes, kernels):
    red = devtrace.reduce(planes, kernels)
    lo, hi = devtrace.window_bounds(planes)
    assert red["window_s"] == pytest.approx(0.4)
    # the same union on a 1 us grid
    grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)
    for _, s, d in _ops(planes):
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=1e-3)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["devices"] == 1


def test_kernel_launches_and_time(planes, kernels):
    red = devtrace.reduce(planes, kernels)
    lo, hi = devtrace.window_bounds(planes)
    launches = [(s, d) for n, s, d in _ops(planes)
                if n.startswith("%probe_blocks") and s + d > lo and s < hi]
    k = red["kernels"]["cosine_topk"]
    assert k["launches"] == len(launches) > 0
    assert k["seconds"] == pytest.approx(sum(d for _, d in launches) * 1e-9)
    # a masked scan of up to 2**20 x 1152 f32 rows: milliseconds per launch
    assert 1e-3 < k["seconds"] / k["launches"] < 20e-3
    # the module line repeats the ops under jit names: never counted
    assert not any("jit_" in n for n, _ in red["device_ops"])


def test_breakdown_lists_ops_and_named_idle_gaps(planes, kernels):
    red = devtrace.reduce(planes, kernels, top=10)
    assert 0 < len(red["device_ops"]) <= 10
    times = [t for _, t in red["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert 0 < len(red["idle_gaps"]) <= 10
    gaps = [t for _, t in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red["window_s"] - red["busy_s"] + 1e-9
    assert all(isinstance(n, str) and n for n, _ in red["idle_gaps"])


def test_a_trace_without_the_window_span_is_refused(planes, kernels):
    stripped = [{**p, "lines": [
        {**ln, "events": [e for e in ln["events"]
                          if e[0] != devtrace.WINDOW_SPAN]}
        for ln in p["lines"]]} for p in planes]
    with pytest.raises(RuntimeError):
        devtrace.reduce(stripped, kernels)
