"""95th-percentile plan latency over every plan started in the window.
Both cells run saturated closed loops, where the tail follows the
coalescer's batching regime from run to run, so it is read per layer."""

import numpy as np

LAYER, UNIT, MOVES = "coalescer", "ms", "plan_ms.p50"


def read(ctx):
    lat = ctx.window["plan_ms"]
    return float(np.percentile(lat, 95)) if len(lat) else None
