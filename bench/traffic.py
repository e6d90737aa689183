"""One general generator for every traffic mix.

A mix is a JSON file under ``bench/traffic/<name>.json`` of parameters:

``sessions``        closed-loop clients; each sends its next query as soon
                    as its last plan returned
``filters``         ``[min, max]`` filters per query, uniform
``pool``            where filters come from: ``"predicate_nodes"`` (up to
                    ``max_per_depth`` nodes of every depth of the concept
                    tree, the paper's protocol) or ``"leaves"`` (the tree's
                    leaf concepts)
``max_per_depth``   for ``"predicate_nodes"``
``repeat_share``    share of queries that repeat an earlier query exactly
                    (same filters, same phrasing): cache hits, or coalesced
                    duplicates when the earlier one is still in flight
``repeat_lag_max``  how far back a repeat reaches, in queries
``warmup_plans``    plans served in set-up, from a stream of their own
``sample_filters``  answers compared with the float64 reference per run

Every other query is phrased afresh: a new paraphrase seed gives a new
embedding, so it misses the predicate cache. Query ``i`` of a stream is a
function of (seed, stream, i) alone, so the same seed gives the same
queries in the same order, however many a run serves.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

WINDOW, WARMUP = 0, 1          # query streams


@dataclasses.dataclass(frozen=True)
class Query:
    index: int
    nodes: tuple                # filter node ids, in submission order
    paraphrase: int             # the phrasing seed passed to the planner
    repeat_of: int              # index of the query it repeats, or -1


def load_mix(root: Path, name: str) -> dict:
    """The traffic mix ``name`` from ``<root>/bench/traffic/<name>.json``."""
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    lo, hi = mix["filters"]
    if not 1 <= lo <= hi:
        raise ValueError(f"{path}: filters {mix['filters']}")
    if mix["sessions"] < 1:
        raise ValueError(f"{path}: sessions {mix['sessions']}")
    return mix


def filter_pool(mix: dict, catalog) -> list[int]:
    if mix["pool"] == "predicate_nodes":
        return catalog.predicate_nodes(mix.get("max_per_depth", 8))
    if mix["pool"] == "leaves":
        return list(catalog.leaves)
    raise ValueError(f"unknown filter pool {mix['pool']!r}")


class QueryStream:
    """Queries of one stream of a mix, drawn lazily and deterministically."""

    def __init__(self, mix: dict, pool: list[int], seed: int, stream: int):
        lo, hi = mix["filters"]
        if hi > len(pool):
            raise ValueError(f"{hi} filters per query from a pool of "
                             f"{len(pool)}")
        self.mix, self.pool = mix, np.asarray(pool, np.int64)
        self.lo, self.hi = lo, hi
        self.seed, self.stream = int(seed), int(stream)
        self.repeat_share = float(mix.get("repeat_share", 0.0))
        self.repeat_lag_max = int(mix.get("repeat_lag_max", 1))

    def _fresh(self, i: int, rng) -> Query:
        k = int(rng.integers(self.lo, self.hi + 1))
        nodes = tuple(int(x) for x in rng.choice(self.pool, size=k,
                                                 replace=False))
        return Query(i, nodes, int(rng.integers(0, 2 ** 31)), -1)

    def get(self, i: int) -> Query:
        rng = np.random.default_rng([self.seed, self.stream, i])
        repeat = rng.random() < self.repeat_share
        lag = int(rng.integers(1, self.repeat_lag_max + 1))
        if repeat and i - lag >= 0:
            src = self.get(i - lag)
            return Query(i, src.nodes, src.paraphrase,
                         src.index if src.repeat_of < 0 else src.repeat_of)
        return self._fresh(i, rng)
