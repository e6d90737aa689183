"""The benchmark harness: one cell, one run.

Everything that belongs to one configuration, traffic mix, metric or
cell is found by name under ``bench/``:

* ``BENCHMARK.json`` (the manifest, at the root) names each cell's
  configuration and traffic mix;
* ``bench/configs/<config>.json``: catalog preset, rows, width, probe
  implementation, index clusters, the serving stack's and the
  coalescer's parameters;
* ``bench/traffic/<mix>.json``: the query mix (``bench/traffic.py``);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``
  returning a number, or None where the run has nothing to read;
* ``bench/limits/<cell>.json``: the limits ``correct`` is decided on.

A run: the catalog (``bench/catalog.py``) and the serving stack through
``repro.launch.serve.build_stack``, both from ``DATA_SEED``, a ``PredicateCoalescer``
behind a thin timed handle, warm-up; then ``sessions`` closed-loop client
threads call ``plan_query`` through the handle for ``seconds``; then the
answers of a seeded sample of the window's plans are compared with the
float64 reference (``bench/reference.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import devtrace
from bench.catalog import as_corpus, make_catalog
from bench.reference import Reference, compare
from bench.traffic import WARMUP, WINDOW, QueryStream, filter_pool, load_mix

ROOT = Path(__file__).resolve().parents[1]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# The catalog, the index and the estimators of every run come from this
# seed; the run seed draws the traffic. Data drawn per run seed changes
# the work from seed to seed: the kv-batch store's sample (distinct
# medoids of 32) takes another size, so set-up compiles programs of new
# shapes, and the index cell's latency follows its clusters.
DATA_SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- manifest


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(root: Path, manifest: dict, name: str) -> dict:
    entry = find(manifest["configs"], name, "configuration")
    return json.loads((Path(root) / entry["file"]).read_text())


def load_limits(root: Path, workload: str) -> dict:
    return json.loads((Path(root) / "bench" / "limits"
                       / f"{workload}.json").read_text())["limits"]


def load_reader(root: Path, name: str):
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(manifest: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones with
    ``trace`` off, the per-layer ones with it on. An entry with a
    ``workloads`` key applies to those cells only; a per-layer entry
    without one applies wherever its ``moves`` metric is reported."""
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# --------------------------------------------------------- timed handle


class TimedHandle:
    """What the planner's clients call: exposes ``probe_outcomes`` and
    delegates to one ``PredicateCoalescer``. Per client thread it adds up
    the time spent inside and keeps each call's outcomes."""

    def __init__(self, coalescer, annotate: bool = False):
        self.coalescer = coalescer
        self.annotate = annotate
        self._local = threading.local()

    def begin(self) -> None:
        self._local.inside = 0.0
        self._local.outcomes = []

    def end(self) -> tuple[float, list]:
        return self._local.inside, self._local.outcomes

    def probe_outcomes(self, preds, thresholds, **kw):
        t0 = time.perf_counter()
        try:
            if self.annotate:
                import jax

                with jax.profiler.TraceAnnotation("bench.probe_outcomes"):
                    res = self.coalescer.probe_outcomes(preds, thresholds,
                                                        **kw)
            else:
                res = self.coalescer.probe_outcomes(preds, thresholds, **kw)
        finally:
            self._local.inside += time.perf_counter() - t0
        self._local.outcomes.append(list(res))
        return res


@dataclasses.dataclass
class PlanRecord:
    query: object               # traffic.Query
    t_submit: float
    t_done: float
    inside_s: float             # time inside the timed handle
    outcomes: list              # per probe call, ProbeOutcome per filter
    plan: object | None         # repro QueryPlan
    error: str | None


def serve_loop(handle, estimator, stream: QueryStream, *, sessions: int,
               seconds: float | None = None, count: int | None = None,
               annotate: bool = False
               ) -> tuple[list[PlanRecord], float, float]:
    """Closed loop: ``sessions`` threads each plan their next query as
    soon as their last plan returned, until ``seconds`` have passed (no
    new plan starts after that) or ``count`` plans were started. Returns
    (records of every plan started, t_start, t_end)."""
    from repro.core.optimizer import plan_query

    lock = threading.Lock()
    nxt = [0]
    records: list[PlanRecord] = []
    t_start = time.perf_counter()
    t_end = t_start + seconds if seconds is not None else float("inf")

    def session():
        import contextlib

        import jax

        while True:
            with lock:
                i = nxt[0]
                if time.perf_counter() >= t_end or (
                        count is not None and i >= count):
                    return
                nxt[0] += 1
            q = stream.get(i)
            handle.begin()
            span = (jax.profiler.TraceAnnotation("bench.plan") if annotate
                    else contextlib.nullcontext())
            t0 = time.perf_counter()
            plan, err = None, None
            try:
                with span:
                    plan = plan_query(list(q.nodes), estimator,
                                      seed=q.paraphrase, coalescer=handle)
            except Exception as e:  # noqa: BLE001 — a failed plan counts
                err = f"{type(e).__name__}: {e}"
            t1 = time.perf_counter()
            inside, outcomes = handle.end()
            records.append(PlanRecord(q, t0, t1, inside, outcomes, plan,
                                      err))

    threads = [threading.Thread(target=session, name=f"bench-session-{s}")
               for s in range(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if seconds is None:
        t_end = time.perf_counter()
    return records, t_start, t_end


def window_summary(records: list[PlanRecord], t_start: float,
                   t_end: float) -> dict:
    """End-to-end numbers of a window: every plan started in it counts
    for the latencies; plans that returned by ``t_end`` count for the
    rate over the whole window."""
    seconds = t_end - t_start
    ok = [r for r in records if r.error is None]
    lat = np.asarray([(r.t_done - r.t_submit) * 1e3 for r in ok])
    done = sum(1 for r in ok if r.t_done <= t_end)
    host = np.asarray([(r.t_done - r.t_submit - r.inside_s) * 1e3
                       for r in ok])
    # plans done and their median latency in each tenth of the window:
    # whether a run keeps one regime or changes within the window
    edges = t_start + seconds * np.arange(11) / 10
    tenths = []
    for a, b in zip(edges[:-1], edges[1:]):
        part = [(r.t_done - r.t_submit) * 1e3 for r in ok
                if a < r.t_done <= b]
        tenths.append((len(part), float(np.median(part)) if part else 0.0))
    return {
        "seconds": seconds,
        "tenths": tenths,
        "attempted": len(records),
        "failed": len(records) - len(ok),
        "completed_in_window": done,
        "plans_per_s": done / seconds if seconds > 0 else 0.0,
        "plan_ms": lat,
        "planner_host_ms": host,
    }


# ----------------------------------------------------------------- cell


class Cell:
    """One workload's set-up: catalog, stack, coalescer, timed handle."""

    def __init__(self, root: Path, workload: str, seed: int, *,
                 annotate: bool = False, scale: dict | None = None,
                 data_seed: int = DATA_SEED):
        self.root = Path(root)
        self.manifest = load_manifest(root)
        self.entry = find(self.manifest["workloads"], workload, "workload")
        self.workload = workload
        self.cfg = load_config(root, self.manifest, self.entry["config"])
        if scale:
            self.cfg = {**self.cfg, **scale}
        self.mix = load_mix(root, self.entry["traffic"])
        self.seed = int(seed)
        self.data_seed = int(data_seed)
        self.annotate = annotate

    def build(self) -> None:
        from repro.launch.coalescer import CoalescerConfig, PredicateCoalescer
        from repro.launch.serve import build_stack
        from repro.obs import ObsHub

        cfg = self.cfg
        data_seed = self.data_seed
        t0 = time.perf_counter()
        self.catalog = make_catalog(cfg["preset"], cfg["rows"], cfg["d"],
                                    data_seed)
        log(f"catalog {cfg['preset']}: {self.catalog.n} x {cfg['d']} rows, "
            f"{len(self.catalog.leaves)} leaves "
            f"({time.perf_counter() - t0:.2f}s)")
        t0 = time.perf_counter()
        st = cfg["stack"]
        self.corpus, ests = build_stack(
            cfg["preset"], corpus=as_corpus(self.catalog), impl=cfg["impl"],
            index_clusters=cfg["index_clusters"], sample=st["sample"],
            rate=st["rate"], spec_steps=st["spec_steps"],
            seed=data_seed % (2 ** 31 - 1))
        self.estimator = ests["ensemble"]
        self.hist = self.estimator.hist
        log(f"stack built ({time.perf_counter() - t0:.2f}s); kv-batch "
            f"sample {len(ests['kvbatch'].store.sample_ids)} images")
        if self.hist.index is not None:
            sizes = np.sort(np.asarray(self.hist.index.sizes))
            log(f"index clusters: largest {sizes[-1]} rows "
                f"({100 * sizes[-1] / sizes.sum():.2f}% of {sizes.sum()}), "
                f"sizes p50 {np.median(sizes):.0f}, p90 "
                f"{np.percentile(sizes, 90):.0f}, p99 "
                f"{np.percentile(sizes, 99):.0f}, empty "
                f"{int((sizes == 0).sum())}")
        co = cfg["coalescer"]
        self.hub = ObsHub()
        self.coalescer = PredicateCoalescer(
            self.hist, CoalescerConfig(
                max_batch=co["max_batch"], window_ms=co["window_ms"],
                cache_capacity=co["cache_size"], cache_bits=co["cache_bits"]),
            obs=self.hub)
        self.handle = TimedHandle(self.coalescer, annotate=self.annotate)
        self.pool = filter_pool(self.mix, self.catalog)

    def warm_up(self) -> None:
        """The cell's own shapes: ``warmup_plans`` plans of the mix's
        warm-up stream through the timed handle, then direct probes of
        their filters in every power-of-two batch the traffic can fill
        (for a pruned index, per filter and at random, since its gather
        buckets depend on the predicates)."""
        t0 = time.perf_counter()
        stream = QueryStream(self.mix, self.pool, self.seed, WARMUP)
        recs, _, _ = serve_loop(self.handle, self.estimator, stream,
                                sessions=self.mix["sessions"],
                                count=self.mix["warmup_plans"])
        bad = [r.error for r in recs if r.error]
        if bad:
            raise RuntimeError(f"warm-up plan failed: {bad[0]}")
        embs, thrs, nodes = [], [], []
        for r in recs:
            for node, est in zip(r.plan.filter_order, r.plan.estimates):
                embs.append(self.catalog.text_embedding(
                    node, r.query.paraphrase))
                thrs.append(est.threshold)
                nodes.append(node)
        embs, thrs = np.stack(embs), np.asarray(thrs, np.float32)
        nodes = np.asarray(nodes)
        rng = np.random.default_rng([self.seed, 7])
        # the largest batch this traffic can put in flight
        top = min(self.cfg["coalescer"]["max_batch"],
                  self.mix["sessions"] * self.mix["filters"][1], len(embs))
        batches = []
        b = 1
        while b < 2 * top:
            size = min(b, len(embs))
            if self.cfg["index_clusters"]:
                # a pruned probe's gather bucket follows the clusters its
                # predicates reach: batches of one filter's phrasings reach
                # the small buckets, random batches the large ones
                for node in np.unique(nodes):
                    own = np.flatnonzero(nodes == node)
                    batches.append(rng.choice(own, size=size))
                batches += [rng.choice(len(embs), size=size, replace=False)
                            for _ in range(48)]
            else:
                batches.append(rng.choice(len(embs), size=size,
                                          replace=False))
            b *= 2
        for rows in batches:
            counts, _ = self.hist.probe_batch(embs[rows], thrs[rows], k=1,
                                              use_cache=False)
            np.asarray(counts)
        log(f"warm-up: {len(recs)} plans, {len(batches)} probes in batches "
            f"up to {b // 2} ({time.perf_counter() - t0:.2f}s)")

    def counters(self) -> dict:
        snap = self.hub.registry.snapshot()["counters"]
        out = {k.split(".", 1)[1]: v for k, v in snap.items()
               if k.startswith("coalescer.")}
        idx = self.hist.index
        if idx is not None:
            st = idx.stats()
            out["rows_scanned"] = st["rows_scanned"]
            out["rows_full_equiv"] = st["rows_full_equiv"]
        return out

    def hist_lengths(self) -> dict:
        reg = self.hub.registry
        return {name: reg.histogram(f"serve.{name}_ms").count
                for name in ("queue_wait", "probe")}

    def close(self) -> None:
        self.coalescer.close()


# ------------------------------------------------------------ checking


def sample_filters(records: list[PlanRecord], n: int, seed: int
                   ) -> list[tuple]:
    """A seeded sample of the window's answered filters, as (node,
    paraphrase, threshold f32, count, bucket). Answers that did not come
    from a probe of their own (cache hits, coalesced duplicates) are taken
    first, up to a third of the sample."""
    rows = []
    for r in records:
        if r.plan is None:
            continue
        bucket = {}
        for call in r.outcomes:
            for node, o in zip(r.query.nodes, call):
                bucket[node] = o.bucket
        for node, est in zip(r.plan.filter_order, r.plan.estimates):
            rows.append((int(node), r.query.paraphrase,
                         np.float32(est.threshold), est.selectivity,
                         bucket.get(node, "")))
    rng = np.random.default_rng([seed, 11])
    other = [i for i, x in enumerate(rows) if x[4] != "probe_scored"]
    scored = [i for i, x in enumerate(rows) if x[4] == "probe_scored"]
    take = list(rng.permutation(other)[:n // 3])
    take += list(rng.permutation(scored)[:n - len(take)])
    return [rows[i] for i in sorted(take)]


def check(catalog, sample: list[tuple], limits: dict, failed: int,
          n_rows: int) -> tuple[dict, dict]:
    """(numbers, checks): the sample's counts against the float64
    reference, each number beside its limit."""
    preds = np.stack([catalog.text_embedding(node, para)
                      for node, para, *_ in sample])
    thr = np.asarray([t for _, _, t, *_ in sample], np.float32)
    counts = np.asarray([int(round(sel * n_rows))
                         for _, _, _, sel, _ in sample])
    exact, lo, hi = Reference(catalog.images).counts(preds, thr)
    nums = {"failed_plans": failed, **compare(counts, exact, lo, hi)}
    checks = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    info = {
        "compared": len(sample),
        "buckets": {b: sum(1 for x in sample if x[4] == b)
                    for b in sorted({x[4] for x in sample})},
        "near_rows": int((hi - lo).sum()),
        "preds": preds, "thr": thr, "exact": exact, "lo": lo, "hi": hi,
    }
    return checks, info


# -------------------------------------------------------------- a run


class CompileCounter:
    """Counts backend compilations (compiles and persistent-cache loads)
    from construction to ``close``, and of them the loads from the cache;
    with ``log`` on, also what JAX logs about each (``jax_log_compiles``
    is on for that time only)."""

    def __init__(self, log: bool = True):
        import logging

        import jax

        self.names: list[str] = []
        self.logged: list[str] = []
        self.cache_hits = 0
        self.seconds = 0.0
        self._log = log
        self._handler = logging.Handler()
        self._handler.emit = lambda rec: (
            rec.getMessage().startswith("Compiling")
            and self.logged.append(rec.getMessage()[:400]))
        if log:
            logging.getLogger("jax").addHandler(self._handler)
            jax.config.update("jax_log_compiles", True)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.names.append(f"{kw.get('fun_name', '?')} "
                              f"({duration:.3f}s)")

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def close(self) -> None:
        import logging

        import jax

        if self._log:
            jax.config.update("jax_log_compiles", False)
            logging.getLogger("jax").removeHandler(self._handler)
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_event)


def device_info(devs) -> dict:
    st = devs[0].memory_stats() or {}
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": int(st.get("peak_bytes_in_use", 0))}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, t_main: float | None = None,
        trace_dir: Path | None = None, scale: dict | None = None,
        devs=None, data_seed: int = DATA_SEED) -> dict:
    """One run of one cell. Returns the result object (the last line)."""
    import jax

    t_main = time.perf_counter() if t_main is None else t_main
    devs = jax.devices() if devs is None else devs
    cell = Cell(root, workload, seed, annotate=trace, scale=scale,
                data_seed=data_seed)
    limits = load_limits(root, workload)
    setup_compiles = CompileCounter(log=False)
    cell.build()
    cell.warm_up()
    setup_compiles.close()
    stream = QueryStream(cell.mix, cell.pool, cell.seed, WINDOW)
    c0, h0 = cell.counters(), cell.hist_lengths()
    setup_s = time.perf_counter() - t_main
    log(f"set-up {setup_s:.2f}s; window {seconds}s, "
        f"{cell.mix['sessions']} sessions; programs in set-up "
        f"{len(setup_compiles.names)}, {setup_compiles.cache_hits} of them "
        f"from the compile cache ({setup_compiles.seconds:.2f}s)")

    def window():
        return serve_loop(cell.handle, cell.estimator, stream,
                          sessions=cell.mix["sessions"], seconds=seconds,
                          annotate=trace)

    compiles = CompileCounter()
    if trace:
        tdir = Path(trace_dir or root / ".bench_trace" / workload)
        shutil.rmtree(tdir, ignore_errors=True)
        with devtrace.Capture(tdir), \
                jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            recs, t0, t1 = window()
    else:
        recs, t0, t1 = window()
    compiles.close()
    if trace:
        planes = devtrace.load(tdir)
    win = window_summary(recs, t0, t1)
    c1 = cell.counters()
    reg = cell.hub.registry
    ctx = SimpleNamespace(
        window=win, setup_s=setup_s, cfg=cell.cfg, mix=cell.mix,
        rows=cell.catalog.n, d=cell.cfg["d"],
        itemsize=int(np.dtype(cell.hist.embeddings.dtype).itemsize),
        device_kind=devs[0].device_kind,
        counters={k: c1[k] - c0.get(k, 0) for k in c1},
        hist={name: reg.histogram(f"serve.{name}_ms").values()[h0[name]:]
              for name in h0},
        trace=None)
    log(f"window: {win['attempted']} plans started, "
        f"{win['completed_in_window']} done in {win['seconds']:.3f}s, "
        f"{win['failed']} failed; compilations inside the window: "
        f"{len(compiles.names)} {compiles.names}")
    for msg in compiles.logged:
        log(f"  in the window: {msg}")
    log("window by tenths: plans done " + " ".join(
        f"{n}" for n, _ in win["tenths"]) + "; plan_ms.p50 " + " ".join(
        f"{p:.1f}" for _, p in win["tenths"]))
    log("coalescer in the window: " + ", ".join(
        f"{k} {v}" for k, v in ctx.counters.items() if v))
    device = device_info(devs)
    cell.close()
    metrics_def = metrics_for(cell.manifest, workload, trace)
    if trace:
        kernels = {}
        for m in metrics_def:
            kernels.update(getattr(load_reader(root, m["name"]), "KERNELS",
                                   {}))
        red = devtrace.reduce(planes, kernels)
        ctx.trace = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    metrics = {}
    for m in metrics_def:
        val = load_reader(root, m["name"]).read(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    sample = sample_filters(recs, cell.mix["sample_filters"], cell.seed)
    catalog, n_rows = cell.catalog, cell.hist.n
    failed = win["failed"]
    del cell, recs, ctx
    gc.collect()
    t_ref = time.perf_counter()
    checks, info = check(catalog, sample, limits, failed, n_rows)
    log(f"reference: {info['compared']} filters compared "
        f"({info['buckets']}), rows within the band {info['near_rows']} "
        f"({time.perf_counter() - t_ref:.2f}s)")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    out["_info"] = {**info, "catalog": catalog, "setup_s": setup_s}
    return out


def emit(out: dict) -> None:
    """The result as the last line of standard output, each compared
    number beside its limit as the last lines of standard error."""
    out = {k: v for k, v in out.items() if not k.startswith("_")}
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
