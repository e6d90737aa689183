"""Median queue wait of a probed predicate in the coalescer
(``serve.queue_wait_ms`` observed in the window)."""

import numpy as np

LAYER, UNIT, MOVES = "coalescer", "ms", "plan_ms.p50"


def read(ctx):
    vals = ctx.hist["queue_wait"]
    return float(np.percentile(vals, 50)) if len(vals) else None
