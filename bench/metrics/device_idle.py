"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window."""

LAYER, UNIT, MOVES = "device", "%", "plan_ms.p50"


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t["window_s"] \
        else None
