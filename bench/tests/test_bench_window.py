"""Window arithmetic: a stall inside the window shows end to end."""

import numpy as np

from bench_tiny import ROOT  # noqa: F401
from bench.harness import PlanRecord, window_summary


def _closed_loop(sessions=4, seconds=2.0, service_s=0.01, stall=None):
    """Synthetic closed-loop records: each session back to back, plans of
    ``service_s``; ``stall`` = (period, length): for ``length`` seconds of
    every ``period`` nothing completes, and plans that would are held to
    the stall's end."""
    recs = []
    for s in range(sessions):
        t = 0.001 * s
        while t < seconds:
            done = t + service_s
            if stall and done % stall[0] < stall[1]:
                done += stall[1] - done % stall[0]
            recs.append(PlanRecord(None, t, done, 0.2 * service_s, [],
                                   object(), None))
            t = done
    return recs


def test_stall_lowers_rate_and_raises_tail():
    base = window_summary(_closed_loop(), 0.0, 2.0)
    hit = window_summary(_closed_loop(stall=(0.1, 0.03)), 0.0, 2.0)
    assert hit["plans_per_s"] < base["plans_per_s"]
    p95 = lambda w: np.percentile(w["plan_ms"], 95)
    assert p95(hit) > p95(base)


def test_rate_counts_plans_done_inside_the_window_only():
    recs = _closed_loop(sessions=1, seconds=1.0, service_s=0.1)
    w = window_summary(recs, 0.0, 1.0)
    assert w["attempted"] == len(recs)
    assert w["completed_in_window"] == sum(r.t_done <= 1.0 for r in recs)
    assert w["plans_per_s"] == w["completed_in_window"] / 1.0


def test_failed_plans_count_and_leave_the_latencies():
    recs = _closed_loop(sessions=1, seconds=1.0, service_s=0.1)
    recs[3] = PlanRecord(None, recs[3].t_submit, recs[3].t_done, 0.0, [],
                         None, "RuntimeError: boom")
    w = window_summary(recs, 0.0, 1.0)
    assert w["failed"] == 1
    assert len(w["plan_ms"]) == len(recs) - 1
    np.testing.assert_allclose(w["planner_host_ms"], 0.8 * w["plan_ms"])
