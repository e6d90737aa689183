"""Median wall time of one probe call of the coalescer's flusher
(``serve.probe_ms``): histogram routing, the index's planning and gather,
the kernel and the copy back."""

import numpy as np

LAYER, UNIT, MOVES = "histogram + index", "ms", "plan_ms.p50"


def read(ctx):
    vals = ctx.hist["probe"]
    return float(np.percentile(vals, 50)) if len(vals) else None
