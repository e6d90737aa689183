"""Rows the pruned index scanned over the rows full scans would have
scanned, in the window (index counters ``rows_scanned`` over
``rows_full_equiv``). Nothing to read without an index."""

LAYER, UNIT, MOVES = "index", "%", "plan_ms.p50"


def read(ctx):
    full = ctx.counters.get("rows_full_equiv", 0)
    return 100.0 * ctx.counters["rows_scanned"] / full if full else None
