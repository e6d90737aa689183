"""Mean time per flush in the window that the coalescer's flusher was
free while a batch was due and not yet taken (coalescer counters
``flush_lag_us`` over ``probes_fired``): its wake-up lag."""

LAYER, UNIT, MOVES = "coalescer", "ms", "plan_ms.p50"


def read(ctx):
    lag = ctx.counters.get("flush_lag_us")
    fired = ctx.counters.get("probes_fired", 0)
    return lag / fired / 1e3 if lag is not None and fired else None
