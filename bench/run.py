#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run
builds the cell's catalog and serving stack (the same in every run),
warms up its shapes, then serves the cell's traffic, drawn from the
seed, for ``--seconds`` through
``plan_query`` and the predicate coalescer, and checks a sample of the
answers against a float64 scan. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
from a profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each compared number and its limit.

It runs on the machine it is started on and needs a TPU there: with
another default backend, or fewer chips than the cell asks for, it exits
with status 2 and prints no result. JAX's compilation cache is kept in
``.jax_cache/`` beside ``bench/``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

T_MAIN = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1].strip())
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness

    manifest = harness.load_manifest(ROOT)
    entry = harness.find(manifest["workloads"], args.workload, "workload")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (default backend {devs[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devs) < entry["chips"]:
        print(f"bench: {args.workload} needs {entry['chips']} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    devs = devs[:entry["chips"]]
    harness.log(f"device {devs[0].device_kind} x {len(devs)}; seed "
                f"{args.seed}")
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), root=ROOT, t_main=T_MAIN, devs=devs)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
