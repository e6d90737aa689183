"""Serving driver: the paper's semantic-filter execution engine end-to-end.

``python -m repro.launch.serve --dataset wildlife --filters 3 --queries 5``
``python -m repro.launch.serve --concurrency 8``

Builds the full Semantic-Histogram stack (embedding store, specificity model,
compressed-KV-cache batching on the reduced LLaVA config), then plans and
executes semantic queries, printing per-estimator latency/calls/overhead —
the interactive counterpart of benchmarks/fig4_end_to_end.py.

Planning uses the batched estimator path: ``plan_query`` hands all filters
of a query to ``estimate_batch`` (one batched histogram probe per plan for
specificity/kv-batch/ensemble), so serving many-filter queries scans the
store once per query rather than once per filter. ``--impl pallas`` routes
probes through the fused cosine_topk kernels (interpret mode on CPU).

``--concurrency N`` switches to the cross-query serving path: N worker
threads plan queries concurrently through one shared
``repro.launch.coalescer.PredicateCoalescer`` — predicates from different
in-flight queries merge into a single micro-batched (N, d) x (d, B) probe
(``--window-ms`` / ``--max-batch`` tune the window), and hot predicates
resolve from the LRU predicate cache (``--cache-size`` / ``--cache-bits``)
without any store scan. The run ends with coalescing + cache counters:
probes fired vs predicates requested, dedup piggybacks, hit/miss/eviction.
``--passes`` replays the workload to model hot repeated predicates
(pass 2+ should be nearly all cache hits). Tuning guide: docs/serving.md.

``--index-clusters K`` (PR 3) builds a cluster-pruned probe index
(``repro.index.ClusteredStore``): the store is k-means-partitioned into K
segments and every probe classifies clusters against its threshold with
exact distance bounds, scanning only boundary clusters — identical counts,
a fraction of the rows at low selectivity. The run ends with the index's
scan-fraction counters. Works with every mode above (the coalescer and
cache sit in front of the pruned probe unchanged). Tuning: docs/index.md.

``--shards S`` (PR 4) runs every probe sharded over an S-device
('data',) mesh (``repro.core.histogram.make_sharded_probe``); on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=S`` first to fake S
host devices. Composed with ``--index-clusters K`` it builds a
*per-shard* pruned index (``repro.index.ShardedClusteredStore``, K
k-means clusters per shard): each probe plans all shards on the host and
one shard_map scans only the boundary segments — the run then ends with
the aggregate AND per-shard scan-fraction counters, whose spread shows
boundary-work imbalance across shards. See docs/index.md.

``--split-radius R`` / ``--balance-boundary`` (PR 5) make the *build*
boundary-aware: fat clusters (radius > R) are recursively 2-means-split
until pruning bounds get traction, and with ``--balance-boundary`` the
sharded index is built from a *global* clustering whose clusters are
packed onto shards by boundary mass (size x radius, greedy min-max LPT
under the equal-rows constraint, splitting clusters at shard edges) —
the uniform shard_map bucket means every probe pays the max per-shard
boundary rows, and balancing is what shrinks that max. The build prints
the per-shard boundary-mass spread before/after; results stay bitwise
identical either way. See docs/index.md.

``--deadline-ms`` / ``--max-queue`` / ``--degraded-ok`` (PR 6) arm the
serving control plane on the concurrent path: every plan's probes get a
wall deadline, the coalescer sheds work past the queue watermark, and
with ``--degraded-ok`` any shed / late / breaker-blocked request resolves
to a certified bound-only selectivity interval (from the cluster index's
Cauchy-Schwarz bounds — pass ``--index-clusters``, else the interval is
the trivial [0, 1]) instead of an error; such plans are marked degraded.
``--chaos "seed=1,fail=0.3,delay=0.2,delay-ms=5,kill-at=3"`` injects
seed-deterministic probe failures/delays and a flusher kill to exercise
retries, the breaker, flusher-death propagation, and degradation; the run
ends with the full robustness counter block (shed / degraded / retries /
breaker state / flusher deaths / queue high-watermark). With chaos off
and the control plane unarmed, results are bitwise identical to before.
``--ingest-rate R`` (PR 7) streams R rows/second into the store *while
the concurrent workload runs*: the index becomes a
``repro.index.MutableClusteredStore`` — inserts land in an unindexed
hot tail every probe fully scans, deletes tombstone rows in place, and
once the tail outgrows ``--rebuild-tail-frac`` of the live set a
background thread rebuilds the cluster index (k-means warm start +
shard-sticky repack) and swaps it in atomically under the serve loop.
Counts and top-k stay exact at every interleaving; the predicate cache
keys on the store version so mutations can never serve stale counts.
The run ends with the mutation counters (inserts / deletes / rebuilds /
tail occupancy). Needs ``--index-clusters`` and ``--concurrency``.
All knobs: docs/serving.md.

Telemetry (PR 8): every run records into one ``repro.obs``
MetricsRegistry — coalescer counters, per-phase latency histograms
(queue-wait / probe / combine / request, exact p50/p95/p99), index
scan-fraction gauges, and live per-estimator q-error measured against
ground truth after each plan executes. The exit summary is rendered
from that registry snapshot; ``--metrics-json PATH`` writes the same
snapshot as schema-versioned JSON, and ``--trace-out PATH`` with
``--trace-sample N`` streams 1-in-N per-request trace spans (submit /
flush / scan / plan / event) as JSONL with a closing reconciliation
summary. Telemetry observes host-side only — probe results stay
bitwise identical with it on or off. ``--profile-dir DIR`` records the
serving phase in a ``jax.profiler`` trace under DIR: the program's spans
(plan, coalescer, histogram and index boundaries; ``repro.obs.spans``)
on the same clock as the device's ops. Schema + tuning:
docs/observability.md.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import numpy as np

from repro.core.estimators import (
    EnsembleEstimator,
    KVBatchEstimator,
    OracleEstimator,
    SamplingEstimator,
    SpecificityEstimator,
)
from repro.core.histogram import SemanticHistogram
from repro.core.kvbatch import build_compressed_store
from repro.core.optimizer import execute_cascade, generate_queries, plan_query
from repro.core.specificity import train_specificity
from repro.core.synthetic import make_corpus, specificity_dataset
from repro.kernels.kmeans.ops import medoid_sample
from repro.launch.coalescer import (
    CoalescerConfig,
    PredicateCache,
    PredicateCoalescer,
)
from repro.obs import ObsHub, Tracer
from repro.obs import report as obs_report


def build_stack(dataset: str, *, n_images: int = 1000, sample: int = 32,
                rate: float = 0.6, spec_steps: int = 600, seed: int = 0,
                impl: str = "xla", index_clusters: int = 0,
                shards: int = 0, split_radius: float = 0.0,
                balance_boundary: bool = False, ingest: bool = False,
                rebuild_tail_frac: float = 0.25, corpus=None):
    """The serving stack over ``corpus``, or over a fresh
    ``make_corpus(dataset, n_images=n_images, seed=seed)`` when None."""
    if corpus is None:
        corpus = make_corpus(dataset, n_images=n_images, seed=seed)
    mesh = None
    if balance_boundary and (shards <= 0 or index_clusters <= 0):
        raise ValueError("--balance-boundary repartitions the sharded "
                         "pruned index — it needs --shards and "
                         "--index-clusters")
    if split_radius > 0 and index_clusters <= 0:
        raise ValueError("--split-radius tunes the pruned-index build — "
                         "it needs --index-clusters")
    if shards > 0:
        from repro.launch.mesh import make_probe_mesh

        mesh = make_probe_mesh(shards)
        print(f"mesh: {shards} probe shard(s), "
              f"{corpus.images.shape[0] // shards} rows each")
    index = None
    sr = split_radius if split_radius > 0 else None
    if ingest:
        if index_clusters <= 0:
            raise ValueError("--ingest-rate streams into the mutable "
                             "cluster index — it needs --index-clusters")
        from repro.index import MutableClusteredStore

        index = MutableClusteredStore(
            corpus.images, index_clusters, mesh=mesh, impl=impl,
            seed=seed, split_radius=sr,
            rebuild_tail_frac=rebuild_tail_frac)
        print(f"index: mutable, {index_clusters} clusters over "
              f"{index.n_live} rows"
              + (f", {shards} shards" if mesh is not None else "")
              + f", rebuild_tail_frac={rebuild_tail_frac}")
    elif index_clusters > 0 and mesh is not None:
        from repro.index import build_sharded_clustered_store

        index = build_sharded_clustered_store(
            corpus.images, index_clusters, shards, seed=seed, impl=impl,
            balance="boundary" if balance_boundary else "contiguous",
            split_radius=sr)
        print(f"index: {index.n_shards} shards x ~{index.k_clusters} "
              f"clusters over {index.n} rows ({index.balance} partition"
              f"{f', split_radius={split_radius}' if sr else ''})")
        mass = index.boundary_mass()
        if index.contiguous_mass is not None:
            cm = index.contiguous_mass
            print(f"boundary mass/shard: contiguous "
                  f"[{', '.join(f'{m:.0f}' for m in cm)}] "
                  f"(spread {cm.max() - cm.min():.0f}) -> balanced "
                  f"[{', '.join(f'{m:.0f}' for m in mass)}] "
                  f"(spread {mass.max() - mass.min():.0f})")
        else:
            print(f"boundary mass/shard: "
                  f"[{', '.join(f'{m:.0f}' for m in mass)}] "
                  f"(spread {mass.max() - mass.min():.0f}; "
                  f"--balance-boundary repartitions to even it out)")
    elif index_clusters > 0:
        from repro.index import build_clustered_store

        index = build_clustered_store(corpus.images, index_clusters,
                                      seed=seed, impl=impl,
                                      split_radius=sr)
        print(f"index: {index.k_clusters} clusters over {index.n} rows "
              f"(radii p50={float(np.median(index.radii)):.3f}"
              f"{f', split_radius={split_radius}' if sr else ''})")
    hist = SemanticHistogram(jax.numpy.asarray(corpus.images), impl=impl,
                             mesh=mesh, index=index)
    X, y = specificity_dataset(corpus, n_samples=2000, seed=seed)
    from repro.configs.paper_stack import SpecificityModelConfig

    model, mtr = train_specificity(
        X, y, SpecificityModelConfig(embed_dim=corpus.dim, steps=spec_steps))
    # cluster the store already on the device: no second device copy
    ids = medoid_sample(hist.embeddings, sample, iters=5, seed=seed)
    store = build_compressed_store(corpus.images, ids, rate=rate, seed=seed)
    spec = SpecificityEstimator(corpus, hist, model)
    kvb = KVBatchEstimator(corpus, hist, store)
    return corpus, {
        "specificity": spec,
        "kvbatch": kvb,
        "ensemble": EnsembleEstimator(spec, kvb),
        "sampling-16": SamplingEstimator(corpus, 16),
        "oracle": OracleEstimator(corpus),
    }


def serve_sequential(corpus, estimators, queries, *, seed: int,
                     obs: ObsHub | None = None,
                     compound: bool = False,
                     feedback: bool = False) -> None:
    """Original per-query driver: every estimator, one query at a time.

    ``compound`` orders multi-filter plans by conditional selectivity
    (estimators exposing ``compound_selectivity``); ``feedback`` turns on
    the ensemble's learned write-back loop with a dedicated
    observed-selectivity cache."""
    oracle = estimators["oracle"]
    if feedback:
        ens = estimators.get("ensemble")
        if ens is not None and ens.observed_cache is None:
            ens.feedback = True
            ens.observed_cache = PredicateCache(1024)
    for qi, q in enumerate(queries):
        base = execute_cascade(corpus, plan_query(q, oracle), seed=seed)
        print(f"\nquery {qi}: filters={q}  oracle calls={base.vlm_calls}")
        for name, est in estimators.items():
            if name == "oracle":
                continue
            fb = est if (feedback and hasattr(est, "observe")) else None
            res = execute_cascade(
                corpus, plan_query(q, est, seed=seed, compound=compound),
                seed=seed, obs=obs, est_name=name, feedback=fb)
            overhead = res.total_s - base.total_s
            print(f"  {name:14s} calls={res.vlm_calls:5d} "
                  f"est_lat={res.plan.est_latency_s*1e3:8.1f}ms "
                  f"overhead={overhead:+8.2f}s  |result|={len(res.result_ids)}")


def serve_concurrent(corpus, estimators, queries, *, est_name: str,
                     seed: int, concurrency: int, window_ms: float,
                     max_batch: int, cache_size: int, cache_bits: int,
                     passes: int, deadline_ms: float = 0.0,
                     max_queue: int = 0, degraded_ok: bool = False,
                     chaos_spec: str = "", ingest_rate: float = 0.0,
                     obs: ObsHub | None = None, compound: bool = False,
                     feedback: bool = False, replicas: int = 1,
                     hedge_ms: float = 0.0,
                     heartbeat_ms: float = 50.0) -> dict:
    """Cross-query serving: N planner threads share one coalescer + cache.

    The control plane rides along per request: each plan's probes carry the
    deadline, the coalescer sheds past ``max_queue``, and ``degraded_ok``
    turns overload/fault resolutions into certified bound-only answers. A
    failing query is a *partial* failure — its worker records the error and
    the rest of the workload proceeds. ``obs`` (an ``repro.obs.ObsHub``)
    collects counters / latency histograms / q-error accounting / trace
    spans; the exit summary is rendered by the caller from its registry.
    Returns the coalescer stats dict (the smoke harness asserts on it).

    ``replicas > 1`` (PR 10) serves through a ``repro.launch.fleet``
    ``ReplicaSet`` instead of one coalescer: R replicas over the same
    store build, predicates routed by cache affinity with health-checked
    failover, optional hedged duplicates (``hedge_ms``), heartbeat
    monitoring (``heartbeat_ms``), and replica-scoped chaos keys in
    ``chaos_spec`` (``replica-kill=R@N`` / ``replica-slow=R@N:MS`` /
    ``partition=R@A-B``). Returns the fleet stats dict (it carries a
    ``replicas`` list — that's how the caller tells the two shapes
    apart)."""
    est = estimators[est_name]
    obs = obs if obs is not None else ObsHub()
    cache = PredicateCache(cache_size, bits=cache_bits)
    if feedback and hasattr(est, "observe"):
        # the serving predicate cache doubles as the observed-selectivity
        # store: same quantization, same LRU discipline, version-keyed
        # (with a fleet this cache only holds observed selectivities —
        # the probe caches live inside the replicas)
        est.feedback = True
        est.observed_cache = cache
    chaos = fleet_chaos = None
    if chaos_spec and replicas > 1:
        from repro.launch.chaos import FleetChaos, FleetChaosConfig

        fleet_chaos = FleetChaos(FleetChaosConfig.parse(chaos_spec),
                                 obs=obs)
    elif chaos_spec:
        from repro.launch.chaos import ChaosConfig, ChaosInjector

        chaos = ChaosInjector(ChaosConfig.parse(chaos_spec), obs=obs)
    workload = [(p, qi, q) for p in range(passes)
                for qi, q in enumerate(queries)]
    n_preds = sum(len(q) for _, _, q in workload)
    print(f"\nconcurrent serve: {len(workload)} queries "
          f"({len(queries)} x {passes} passes), {n_preds} predicate "
          f"requests, estimator={est_name}, threads={concurrency}, "
          f"window={window_ms}ms, max_batch={max_batch}, "
          f"cache={cache_size}x{cache_bits}bit"
          + (f", replicas={replicas}" if replicas > 1 else "")
          + (f", hedge={hedge_ms}ms" if hedge_ms else "")
          + (f", deadline={deadline_ms}ms" if deadline_ms else "")
          + (f", max_queue={max_queue}" if max_queue else "")
          + (", degraded-ok" if degraded_ok else "")
          + (f", chaos[{chaos_spec}]" if chaos_spec else "")
          + (f", ingest={ingest_rate}/s" if ingest_rate else ""))

    index = est.hist.index
    stop_ingest = threading.Event()
    ingest_thread = None
    if ingest_rate > 0:
        if index is None or not getattr(index, "is_mutable", False):
            raise ValueError("--ingest-rate needs the mutable index "
                             "(build the stack with ingest=True)")

        def ingest_loop():
            rng = np.random.default_rng(seed + 0x1735)
            period = 1.0 / ingest_rate
            mine: list[int] = []
            while not stop_ingest.is_set():
                x = rng.normal(size=(1, corpus.dim)).astype(np.float32)
                x /= np.linalg.norm(x)
                mine.extend(int(i) for i in index.insert(x))
                # ~30% churn: retire an earlier streamed row now and then
                if len(mine) >= 8 and rng.random() < 0.3:
                    index.delete([mine.pop(int(rng.integers(len(mine))))])
                stop_ingest.wait(period)

        ingest_thread = threading.Thread(target=ingest_loop,
                                         name="serve-ingest", daemon=True)
        ingest_thread.start()

    ccfg = CoalescerConfig(max_batch=max_batch, window_ms=window_ms,
                           cache_capacity=cache_size,
                           cache_bits=cache_bits, max_queue=max_queue)
    if replicas > 1:
        from repro.launch.fleet import FleetConfig, ReplicaSet

        # every replica gets its own store HANDLE over the same arrays /
        # index object — bitwise-identical probes, one copy of the data
        hists = [est.hist] + [
            SemanticHistogram(est.hist.embeddings, mesh=est.hist.mesh,
                              impl=est.hist.impl, index=est.hist.index)
            for _ in range(replicas - 1)]
        serving = ReplicaSet(
            hists, ccfg,
            fleet=FleetConfig(replicas=replicas, hedge_ms=hedge_ms,
                              heartbeat_ms=heartbeat_ms,
                              max_replica_queue=max_queue),
            chaos=fleet_chaos, obs=obs)
    else:
        serving = PredicateCoalescer(est.hist, ccfg, cache=cache,
                                     chaos=chaos, obs=obs)

    failures: list[tuple[int, str]] = []
    plan_ms = obs.registry.histogram("serve.plan_ms")
    with serving as coal:

        def run_one(job):
            _, qi, q = job
            t_q = time.perf_counter()
            try:
                plan = plan_query(q, est, seed=seed, coalescer=coal,
                                  deadline_ms=deadline_ms or None,
                                  degraded_ok=degraded_ok,
                                  compound=compound)
            except Exception as e:  # noqa: BLE001 — partial failure
                failures.append((qi, f"{type(e).__name__}: {e}"))
                return qi, None, False
            plan_ms.observe((time.perf_counter() - t_q) * 1e3)
            fb = est if (feedback and hasattr(est, "observe")) else None
            res = execute_cascade(corpus, plan, seed=seed, obs=obs,
                                  est_name=est_name, feedback=fb)
            tr = obs.tracer
            if tr is not None and tr.sample_hit("plan"):
                tr.emit("plan", query=int(qi), estimator=est_name,
                        degraded=bool(plan.degraded),
                        est_ms=round(plan.est_latency_s * 1e3, 3),
                        wall_ms=round((time.perf_counter() - t_q) * 1e3,
                                      3),
                        vlm_calls=int(res.vlm_calls))
            return qi, res, plan.degraded

        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=concurrency) as pool:
            results = list(pool.map(run_one, workload))
        wall_s = time.perf_counter() - t0
        if ingest_thread is not None:
            stop_ingest.set()
            ingest_thread.join(timeout=10.0)
            index.drain_rebuild(timeout=120.0)
        stats = coal.stats()

    degraded_plans = sum(1 for _, _, dg in results if dg)
    oracle = estimators["oracle"]
    for qi, res, _ in results[:len(queries)]:
        if res is None:
            print(f"  query {qi}: FAILED")
            continue
        base = execute_cascade(corpus, plan_query(queries[qi], oracle),
                               seed=seed)
        print(f"  query {qi}: calls={res.vlm_calls:5d} "
              f"(oracle {base.vlm_calls}) |result|={len(res.result_ids)}")

    # Everything the run learned goes through the registry: the exit
    # summary (obs.report.render) and --metrics-json are both views of
    # the same snapshot, so the human block can never drift from the
    # machine one.
    reg = obs.registry
    reg.counter("serve.queries").inc(len(workload))
    reg.counter("serve.degraded_plans").inc(degraded_plans)
    reg.counter("serve.failed_queries").inc(len(failures))
    reg.gauge("serve.wall_s").set(wall_s)
    reg.gauge("serve.qps").set(len(workload) / wall_s if wall_s else 0.0)
    if failures:
        print(f"  first failure: {failures[0][1]}")
    return stats


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself), or
    else at ``<repo>/.jax_cache``: a fixed path, so one run's compiled
    programs are found again by the next. Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="wildlife",
                    choices=["wildlife", "artwork", "ecommerce"])
    ap.add_argument("--filters", type=int, default=3)
    ap.add_argument("--queries", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"],
                    help="histogram probe backend (pallas = fused kernel, "
                         "interpret mode on CPU)")
    ap.add_argument("--index-clusters", type=int, default=0,
                    help=">0: build a cluster-pruned probe index with this "
                         "many k-means clusters — probes scan only boundary "
                         "clusters (exact counts, sublinear at low "
                         "selectivity); with --shards, K clusters per shard")
    ap.add_argument("--shards", type=int, default=0,
                    help=">0: shard every probe over this many devices "
                         "(('data',) mesh; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count first). "
                         "Composes with --index-clusters: per-shard pruned "
                         "probes, per-shard scan counters at exit")
    ap.add_argument("--split-radius", type=float, default=0.0,
                    help=">0: split fat clusters at index build until "
                         "every cluster's radius fits this budget (local "
                         "2-means, widest first) — fixes the one-wide-"
                         "cluster pathology that defeats pruning")
    ap.add_argument("--balance-boundary", action="store_true",
                    help="with --shards + --index-clusters: cluster "
                         "globally and pack clusters onto shards by "
                         "boundary mass (size x radius, min-max LPT under "
                         "equal rows/shard) instead of taking contiguous "
                         "row blocks — evens the max per-shard boundary "
                         "rows every probe pays; prints the before/after "
                         "per-shard mass spread")
    ap.add_argument("--concurrency", type=int, default=1,
                    help=">1: plan queries from this many threads through "
                         "a shared predicate coalescer + LRU cache")
    ap.add_argument("--estimator", default="ensemble",
                    choices=["specificity", "kvbatch", "ensemble"],
                    help="estimator for the concurrent path")
    ap.add_argument("--window-ms", type=float, default=4.0,
                    help="micro-batch window: max wait before a partial "
                         "batch flushes")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="micro-batch window: flush at this many pending "
                         "predicates")
    ap.add_argument("--cache-size", type=int, default=1024,
                    help="LRU predicate-cache capacity (entries)")
    ap.add_argument("--cache-bits", type=int, default=12,
                    help="embedding quantization bits for cache keys")
    ap.add_argument("--passes", type=int, default=2,
                    help="replay the query workload this many times "
                         "(models hot repeated predicates)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help=">0: wall deadline per plan's probes; past it the "
                         "request degrades to a certified bound-only "
                         "answer (--degraded-ok) or fails, never hangs")
    ap.add_argument("--max-queue", type=int, default=0,
                    help=">0: admission control — shed new predicates once "
                         "this many are pending (bound-only answer with "
                         "--degraded-ok, ShedError without)")
    ap.add_argument("--degraded-ok", action="store_true",
                    help="resolve shed/late/breaker-blocked requests with "
                         "certified selectivity bounds (cluster-index "
                         "Cauchy-Schwarz interval; [0,1] without an index) "
                         "instead of raising; plans are marked degraded")
    ap.add_argument("--ingest-rate", type=float, default=0.0,
                    help=">0: stream this many rows/second into the store "
                         "while the concurrent workload runs — switches "
                         "--index-clusters to the mutable store (hot-tail "
                         "inserts, tombstone deletes, background rebuilds); "
                         "needs --concurrency > 1")
    ap.add_argument("--rebuild-tail-frac", type=float, default=0.25,
                    help="mutable store: trigger a background index "
                         "rebuild once the unindexed hot tail exceeds "
                         "this fraction of live rows")
    ap.add_argument("--chaos", default="",
                    help="deterministic fault injection on the probe path, "
                         "e.g. 'seed=1,fail=0.3,delay=0.2,delay-ms=5,"
                         "kill-at=3' — seeded probe failures/delays and a "
                         "flusher kill at the given launch ordinal; with "
                         "--replicas also replica-scoped faults keyed by "
                         "fleet dispatch ordinal: 'replica-kill=1@6', "
                         "'replica-slow=2@3:25', 'partition=0@4-9'")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1: serve through a replicated fleet — this many "
                         "independent replicas (own coalescer, predicate "
                         "cache, breaker) over the same store build, with "
                         "cache-affinity consistent-hash routing and "
                         "health-checked ring-successor failover; needs "
                         "--concurrency > 1")
    ap.add_argument("--hedge-ms", type=float, default=0.0,
                    help=">0 with --replicas: fire a hedged duplicate at "
                         "the key's next healthy replica when a dispatch "
                         "hasn't landed within this budget; first "
                         "completion wins, the loser is accounted "
                         "hedge_cancelled")
    ap.add_argument("--heartbeat-ms", type=float, default=50.0,
                    help="fleet health monitor period: replicas missing "
                         "beats for 5x this are routed around until they "
                         "recover (0 disables the monitor)")
    ap.add_argument("--compound", action="store_true",
                    help="order multi-filter plans by conditional (joint) "
                         "selectivity through the index's one-launch "
                         "compound probe instead of the independence "
                         "assumption (estimators exposing "
                         "compound_selectivity; see docs/index.md)")
    ap.add_argument("--feedback", action="store_true",
                    help="Larch-style learned loop: after each executed "
                         "plan, write observed per-filter and per-prefix "
                         "selectivities back into the ensemble's "
                         "correction weights and the version-keyed "
                         "observed-selectivity cache")
    ap.add_argument("--n-images", type=int, default=1000,
                    help="corpus size (rows in the embedding store)")
    ap.add_argument("--metrics-json", default="",
                    help="write the exit metrics snapshot (counters, "
                         "latency/q-error histograms, reconciliation) to "
                         "this path as schema-versioned JSON — the same "
                         "snapshot the human summary renders")
    ap.add_argument("--trace-out", default="",
                    help="write sampled per-request trace spans (submit/"
                         "flush/scan/plan/event + a closing summary) to "
                         "this path as JSONL")
    ap.add_argument("--trace-sample", type=int, default=1,
                    help="trace 1-in-N requests per span kind (1 = every "
                         "request; raise under load to bound overhead)")
    ap.add_argument("--profile-dir", default="",
                    help="record the serving phase in a jax.profiler "
                         "trace under this directory: the program's "
                         "spans beside the device's ops (open with "
                         "TensorBoard's profile plugin, or read with "
                         "jax.profiler.ProfileData)")
    args = ap.parse_args(argv)

    if args.ingest_rate > 0 and args.concurrency <= 1:
        ap.error("--ingest-rate streams during the concurrent serve "
                 "path — it needs --concurrency > 1")
    if args.replicas > 1 and args.concurrency <= 1:
        ap.error("--replicas serves through the concurrent path — it "
                 "needs --concurrency > 1")
    use_compile_cache()
    tracer = (Tracer(args.trace_out, sample=args.trace_sample)
              if args.trace_out else None)
    hub = ObsHub(tracer=tracer)
    print(f"building semantic-histogram stack for '{args.dataset}' "
          f"(probe impl={args.impl})...")
    corpus, estimators = build_stack(args.dataset, seed=args.seed,
                                     n_images=args.n_images,
                                     impl=args.impl,
                                     index_clusters=args.index_clusters,
                                     shards=args.shards,
                                     split_radius=args.split_radius,
                                     balance_boundary=args.balance_boundary,
                                     ingest=args.ingest_rate > 0,
                                     rebuild_tail_frac=args.rebuild_tail_frac)
    index = estimators["specificity"].hist.index
    if index is not None:
        index.obs = hub
    queries = generate_queries(corpus, n_queries=args.queries,
                               n_filters=args.filters, seed=args.seed)
    stats = None
    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    with profile:
        if args.concurrency > 1:
            stats = serve_concurrent(
                corpus, estimators, queries, est_name=args.estimator,
                seed=args.seed, concurrency=args.concurrency,
                window_ms=args.window_ms, max_batch=args.max_batch,
                cache_size=args.cache_size, cache_bits=args.cache_bits,
                passes=args.passes, deadline_ms=args.deadline_ms,
                max_queue=args.max_queue, degraded_ok=args.degraded_ok,
                chaos_spec=args.chaos, ingest_rate=args.ingest_rate,
                obs=hub, compound=args.compound, feedback=args.feedback,
                replicas=args.replicas, hedge_ms=args.hedge_ms,
                heartbeat_ms=args.heartbeat_ms)
        else:
            serve_sequential(corpus, estimators, queries, seed=args.seed,
                             obs=hub, compound=args.compound,
                             feedback=args.feedback)
    if args.profile_dir:
        print(f"profiler trace -> {args.profile_dir}")
    is_fleet = stats is not None and "replicas" in stats
    snap = obs_report.build_snapshot(
        registry=hub.registry,
        coalescer=None if is_fleet else stats,
        fleet=stats if is_fleet else None,
        index=index.stats() if index is not None else None,
        mutable=bool(getattr(index, "is_mutable", False)))
    print()
    print(obs_report.render(snap))
    if is_fleet:
        # the fleet invariant is load-bearing: a serve run that fails to
        # reconcile its counters must not exit 0
        fl = snap["fleet"]
        if not (fl["reconciles"]
                and all(r["reconciles"] for r in fl["replicas"])):
            raise SystemExit(
                "fleet counters do not reconcile (requests != sum of "
                "resolution buckets) — see the fleet block above")
    if args.metrics_json:
        obs_report.write_json(snap, args.metrics_json)
        print(f"metrics snapshot -> {args.metrics_json}")
    if tracer is not None:
        if stats is not None:
            hub.write_trace_summary(stats)
        tracer.close()
        print(f"trace spans -> {args.trace_out} "
              f"({tracer.emitted} records, sample=1/{args.trace_sample})")
    failed = hub.registry.counter("serve.failed_queries").value
    if failed and not args.chaos:
        # without injected faults every query must plan: a run whose
        # probes fail must not exit 0
        raise SystemExit(f"{failed} queries failed — see 'first failure' "
                         f"above")


if __name__ == "__main__":
    main()
