"""A tiny-size copy of the benchmark tree for CPU tests.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/{configs,
traffic,metrics,limits}`` into ``tmp`` and writes limits for the tiny
size; ``TINY`` shrinks a configuration to rows and widths the CPU (and
the Pallas-free XLA probe path) runs in seconds.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CELL1 = "wildlife-1m.miss-c64"
CELL2 = "ecommerce-1m-k1024.leaf-c2"
SEED = 2 ** 31 + 99                 # above 32 signed bits

TINY = {"rows": 16384, "d": 64, "impl": "xla",
        "stack": {"sample": 8, "rate": 0.6, "spec_steps": 20}}
# at 16,384 rows x 64 a float32 scan on the CPU matches float64 on every
# sampled filter, and a bfloat16 scan does not
TINY_LIMITS = {"failed_plans": 0, "band_misses": 0, "count_gap_max": 0,
               "count_gap_sum": 0}


def tiny_scale(workload: str) -> dict:
    return {**TINY, "index_clusters": 64 if workload == CELL2 else 0}


def tiny_root(tmp: Path) -> Path:
    tmp = Path(tmp)
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub)
    for cell in (CELL1, CELL2):
        (tmp / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps({"limits": TINY_LIMITS}))
    return tmp
