"""Plans completed over the whole window's seconds."""

LAYER, UNIT, MOVES = "end to end", "plans/s", None


def read(ctx):
    return ctx.window["plans_per_s"]
