"""Device time per launch of the probe kernel (cosine_topk), from the
profiler trace of the window."""

LAYER, UNIT, MOVES = "kernels", "ms", "plan_ms.p50"
# the pallas_call of cosine_topk, by its op name on the "XLA Ops" line
KERNELS = {"cosine_topk": r"^%probe_blocks(\.\d+)? = "}


def read(ctx):
    k = ctx.trace["kernels"]["cosine_topk"]
    return 1e3 * k["seconds"] / k["launches"] if k["launches"] else None
