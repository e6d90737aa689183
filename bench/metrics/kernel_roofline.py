"""The probe kernel's share of its roofline: the least time the chip
needs for the window's scans (bench/roofline.py) over the kernel's device
time in the trace. Rows: the whole store per launch, or the rows the
pruned index scanned; the predicate panel at the coalescer's mean batch
rounded up to its power-of-two bucket."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.roofline import least_time, probe_work  # noqa: E402

LAYER, UNIT, MOVES = "kernels", "%", "plan_ms.p50"
# the pallas_call of cosine_topk, by its op name on the "XLA Ops" line
KERNELS = {"cosine_topk": r"^%probe_blocks(\.\d+)? = "}


def read(ctx):
    k = ctx.trace["kernels"]["cosine_topk"]
    fired = ctx.counters.get("probes_fired", 0)
    if not k["launches"] or not fired:
        return None
    b = ctx.counters["predicates_probed"] / fired
    bucket = 1 << max(0, int(b - 1e-9)).bit_length()
    rows = ctx.counters.get("rows_scanned")
    if rows is None:
        rows = ctx.rows * k["launches"]
    nbytes, flops = probe_work(rows, ctx.d, ctx.itemsize, bucket)
    nbytes += (k["launches"] - 1) * bucket * ctx.d * 4
    t, _ = least_time(nbytes, flops, ctx.device_kind)
    return 100.0 * t / k["seconds"]
