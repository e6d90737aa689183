#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program's numbers and
the control's, over several seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3

For each seed it makes one run of the cell with a short window (at the
cell's own load and sizes), then puts the float64 reference's scan in the
program's place at lower precisions, on the device, for the same sampled
filters and thresholds: bfloat16 operands with three MXU passes (the
``HIGH`` precision) and with one (the default precision). Each seed prints
one JSON line with the program's and the controls' numbers. A limit holds
when the program stays at or under it and a control goes over it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--data-seed", type=int, default=None,
                    help="draw the catalog, index and estimators from this "
                         "seed instead of the benchmark's fixed one")
    ap.add_argument("--out", default="", help="append the lines here too")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    from bench.reference import compare, control_counts

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        data_seed = (harness.DATA_SEED if args.data_seed is None
                     else args.data_seed)
        out = harness.run(args.workload, seed, args.seconds, False,
                          root=ROOT, devs=devs[:1], data_seed=data_seed)
        info = out.pop("_info")
        cat = info.pop("catalog")
        images = jax.device_put(cat.images, devs[0])
        line = {"workload": args.workload, "seed": seed,
                "data_seed": data_seed,
                "correct": out["correct"],
                "program": {k: c["value"] for k, c in out["checks"].items()},
                "near_rows": info["near_rows"],
                "buckets": info["buckets"], "setup_s": info["setup_s"],
                "metrics": {k: m["value"] for k, m in out["metrics"].items()}}
        for label, passes in (("control_high", 3), ("control_bf16", 1)):
            cc = control_counts(images, info["preds"], info["thr"], passes)
            line[label] = compare(cc, info["exact"], info["lo"], info["hi"])
        line["seconds"] = time.perf_counter() - t0
        del images, cat, info, out
        gc.collect()
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
