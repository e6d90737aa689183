"""Predicates scored per probe launch in the window (coalescer counters
``predicates_probed`` over ``probes_fired``)."""

LAYER, UNIT, MOVES = "coalescer", "preds/probe", "plan_ms.p50"


def read(ctx):
    fired = ctx.counters.get("probes_fired", 0)
    return ctx.counters["predicates_probed"] / fired if fired else None
