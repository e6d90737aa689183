"""Median time of a plan spent outside the coalescer handle: embedding,
specificity-model and kv-batch thresholds, ordering (core/optimizer.py,
core/estimators.py)."""

import numpy as np

LAYER, UNIT, MOVES = "planner + estimators", "ms", "plan_ms.p50"


def read(ctx):
    host = ctx.window["planner_host_ms"]
    return float(np.percentile(host, 50)) if len(host) else None
