"""Median plan latency, submission to returned plan, over every plan
started in the window."""

import numpy as np

LAYER, UNIT, MOVES = "end to end", "ms", None


def read(ctx):
    lat = ctx.window["plan_ms"]
    return float(np.percentile(lat, 50)) if len(lat) else None
