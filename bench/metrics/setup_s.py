"""Set-up time: process start to the measured window (catalog, serving
stack, warm-up, and any compilation or compile-cache loads)."""

LAYER, UNIT, MOVES = "end to end", "s", None


def read(ctx):
    return ctx.setup_s
