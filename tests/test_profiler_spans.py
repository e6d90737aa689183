"""Program spans on the profiler's clock (``repro.obs.spans``) and the
counters at the same boundaries (``flush_lag_us``, ``rows_gathered``).

A tiny served stack (pruned index, coalescer, ``plan_query`` from two
client threads) runs under ``jax.profiler`` on the CPU; the trace is read
back through the benchmark's own loader (``bench.devtrace.load``).
"""

import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.optimizer import plan_query
from repro.launch.coalescer import CoalescerConfig, PredicateCoalescer
from repro.launch.serve import build_stack
from repro.obs import spans

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

# child span -> the span it opens inside, on the same thread
PARENT = {
    spans.PLAN_EMBED: spans.PLAN,
    spans.PLAN_SPECIFICITY: spans.PLAN,
    spans.PLAN_KVBATCH: spans.PLAN,
    spans.PLAN_PROBE: spans.PLAN,
    spans.COALESCER_SUBMIT: spans.PLAN_PROBE,
    spans.HIST_PROBE: spans.COALESCER_FLUSH,
    spans.HIST_COPY_BACK: spans.COALESCER_FLUSH,
    spans.COALESCER_SCATTER: spans.COALESCER_FLUSH,
    spans.INDEX_PLAN_SCAN: spans.HIST_PROBE,
    spans.INDEX_GATHER: spans.HIST_PROBE,
    spans.INDEX_SCAN: spans.HIST_PROBE,
}


@pytest.fixture(scope="module")
def stack():
    corpus, ests = build_stack("wildlife", n_images=2048, sample=8,
                               spec_steps=20, index_clusters=16, seed=0)
    return corpus, ests["ensemble"]


def _serve(est, queries, coal):
    """Plan ``queries`` from two client threads through ``coal``."""
    plans = [None] * len(queries)

    def client(j):
        for i in range(j, len(queries), 2):
            plans[i] = plan_query(queries[i], est, seed=i, coalescer=coal)

    threads = [threading.Thread(target=client, args=(j,)) for j in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return plans


def _queries(corpus, n=6):
    nodes = corpus.predicate_nodes()
    return [list(nodes[i % len(nodes):i % len(nodes) + 3]) for i in range(n)]


def test_every_span_lands_in_the_trace_nested(stack, tmp_path):
    corpus, est = stack
    queries = _queries(corpus)
    coal = PredicateCoalescer(est.hist,
                              CoalescerConfig(max_batch=8, window_ms=5))
    fired0 = coal.stats()["probes_fired"]
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            plans = _serve(est, queries, coal)
            coal.close()        # the last flush span ends inside the trace
    fired = coal.stats()["probes_fired"] - fired0
    assert all(p is not None for p in plans)

    planes = devtrace.load(tmp_path)
    lines = [line["events"] for plane in planes
             if plane["name"].startswith("/host") for line in plane["lines"]]
    seen = {n for evs in lines for n, _, _ in evs}
    assert set(spans.SPANS) <= seen, set(spans.SPANS) - seen

    for evs in lines:
        for name, s, d in evs:
            if name not in PARENT:
                continue
            assert any(n == PARENT[name] and ps <= s and s + d <= ps + pd
                       for n, ps, pd in evs), (name, PARENT[name])
    # the flusher never waits for a batch inside a flush
    for evs in lines:
        flushes = [(s, s + d) for n, s, d in evs
                   if n == spans.COALESCER_FLUSH]
        for n, s, d in evs:
            if n == spans.COALESCER_AWAIT_BATCH:
                assert not any(a < s + d and s < b for a, b in flushes)

    def count(name):
        return sum(n == name for evs in lines for n, _, _ in evs)

    assert count(spans.PLAN) == len(queries)
    assert count(spans.COALESCER_FLUSH) == fired > 0


def test_probe_results_bitwise_equal_with_the_profiler_on(stack, tmp_path):
    corpus, est = stack
    rng = np.random.default_rng(3)
    preds = corpus.images[rng.choice(corpus.images.shape[0], 8)]
    thr = np.linspace(0.2, 0.9, 8).astype(np.float32)
    hist = est.hist

    def probe():
        counts, topk = hist.probe_batch(preds, thr, k=4, use_cache=False)
        return np.asarray(counts), np.asarray(topk)

    off = probe()
    with jax.profiler.trace(str(tmp_path)):
        on = probe()
    np.testing.assert_array_equal(off[0], on[0])
    np.testing.assert_array_equal(off[1], on[1])


def _unit_rows(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _wait_until(cond, timeout=10.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, "condition never held"
        time.sleep(0.001)


def test_flush_lag_counts_only_a_free_flusher_past_a_due_batch():
    from repro.core.histogram import SemanticHistogram

    x = _unit_rows(np.random.default_rng(0), 256, 16)
    hist = SemanticHistogram(jnp.asarray(x))
    coal = PredicateCoalescer(hist, CoalescerConfig(max_batch=4,
                                                    window_ms=50))
    try:
        # nothing pending: waiting on an empty queue is no lag
        time.sleep(0.2)
        assert coal.stats()["flush_lag_us"] == 0

        # held inside a flush: the due batch behind it waits for a busy
        # flusher, which is not lag either
        orig = coal._probe
        coal._probe = lambda e, t: (time.sleep(0.3), orig(e, t))[1]
        ts = [threading.Thread(target=coal.selectivity, args=(x[i], 0.5))
              for i in range(2)]
        ts[0].start()
        _wait_until(lambda: coal.stats()["probes_fired"] == 0
                    and coal.queue_depth() == 0)
        ts[1].start()
        for t in ts:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in ts)
        assert coal.stats()["probes_fired"] == 2
        assert coal.stats()["flush_lag_us"] < 100_000
        coal._probe = orig

        # a batch falls due while the flusher cannot wake (the lock it
        # sleeps on is held, as the interpreter lock can hold it): lag
        before = coal.stats()["flush_lag_us"]
        t = threading.Thread(target=coal.selectivity, args=(x[2], 0.5))
        t.start()
        _wait_until(lambda: coal.queue_depth() == 1)
        with coal._cv:
            time.sleep(0.3)
        t.join(timeout=10)
        assert not t.is_alive()
        lag = coal.stats()["flush_lag_us"] - before
        assert 200_000 <= lag < 2_000_000
    finally:
        coal.close()


def test_flush_lag_leaves_out_time_inside_the_flush():
    from repro.core.histogram import SemanticHistogram

    x = _unit_rows(np.random.default_rng(0), 256, 16)
    hist = SemanticHistogram(jnp.asarray(x))
    coal = PredicateCoalescer(hist, CoalescerConfig(max_batch=4,
                                                    window_ms=20))
    try:
        # the flusher is slow between taking the batch and dispatching
        # it: that is time in the flush, not time the batch sat due
        orig = coal._flush_batch

        def slow_flush(*args):
            time.sleep(0.3)
            orig(*args)

        coal._flush_batch = slow_flush
        coal.selectivity(x[0], 0.5)
        assert coal.stats()["probes_fired"] == 1
        assert coal.stats()["flush_lag_us"] < 100_000
    finally:
        coal.close()


def test_rows_gathered_counts_padding_and_the_whole_store():
    from repro.index import build_clustered_store

    rng = np.random.default_rng(1)
    centers = _unit_rows(rng, 16, 16)
    x = centers[rng.integers(0, 16, 3000)] \
        + 0.05 * rng.standard_normal((3000, 16)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    idx = build_clustered_store(x, 16, seed=0)
    preds = x[:4]
    # a narrow threshold scans the predicates' own blobs, padded to a
    # power-of-two bucket
    _, _, st = idx.probe_pruned(preds, np.full(4, 0.01, np.float32),
                                need_topk=False)
    assert 0 < st["rows_scanned"] < idx.n
    assert st["rows_gathered"] == max(
        128, 1 << (st["rows_scanned"] - 1).bit_length())
    # a threshold near the median distance reaches every cluster: the
    # whole store is read as it lies, with no padding
    _, _, st = idx.probe_pruned(preds, np.full(4, 1.0, np.float32))
    assert st["rows_scanned"] == st["rows_gathered"] == idx.n
    cum = idx.stats()
    assert cum["rows_gathered"] >= cum["rows_scanned"]
    idx.kth_smallest(preds[0], 5)
    cum = idx.stats()
    assert cum["rows_gathered"] >= cum["rows_scanned"]


def test_sharded_rows_gathered_counts_each_shard_bucket():
    from repro.core.histogram import SemanticHistogram
    from repro.index import build_sharded_clustered_store
    from repro.launch.mesh import make_probe_mesh

    rng = np.random.default_rng(1)
    centers = _unit_rows(rng, 16, 16)
    x = centers[rng.integers(0, 16, 3000)] \
        + 0.05 * rng.standard_normal((3000, 16)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    sidx = build_sharded_clustered_store(x, 16, 1, iters=4, impl="xla")
    hist = SemanticHistogram(jnp.asarray(x), mesh=make_probe_mesh(1),
                             index=sidx)
    # a narrow threshold pads the shard's boundary rows to its bucket
    hist.probe_batch(x[:2], np.full(2, 0.01, np.float32), k=4)
    st = sidx.stats()
    assert 0 < st["rows_scanned"] < st["rows_gathered"]
    assert st["rows_gathered"] == sidx.shards[0].stats()["rows_gathered"]
    # near the median distance every cluster is scanned: the whole shard
    # is read as it lies
    sidx.reset_stats()
    hist.probe_batch(x[:2], np.full(2, 1.0, np.float32), k=4)
    st = sidx.stats()
    assert st["rows_gathered"] == st["rows_scanned"] == sidx.shard_rows
    # the compound path records its per-shard bucket too
    sidx.reset_stats()
    hist.count_compound(x[:2], np.asarray([0.6, 0.6], np.float32))
    st = sidx.stats()
    assert 0 < st["rows_scanned"] <= st["rows_gathered"]


def test_device_programs_keep_their_names():
    from repro.core.specificity import SpecificityModel, specificity_specs
    from repro.configs.paper_stack import SpecificityModelConfig
    from repro.index.clustered import gather_rows
    from repro.kernels.cosine_topk import ops as ct
    from repro.models import nn

    store = jnp.zeros((1024, 128), jnp.float32)
    preds = jnp.zeros((8, 128), jnp.float32)
    thr = jnp.zeros((8, 1), jnp.float32)
    texts = {
        "jit_gather_rows": gather_rows.lower(
            store, jnp.arange(256)).as_text(),
        "jit_cosine_probe_batch": ct.cosine_probe_batch.lower(
            store, preds, thr, k=1, interpret=True).as_text(),
        "jit_cosine_probe_batch_masked": ct.cosine_probe_batch_masked.lower(
            store, jnp.asarray(512, jnp.int32), preds, thr, k=1,
            interpret=True).as_text(),
    }
    cfg = SpecificityModelConfig(embed_dim=128)
    model = SpecificityModel(
        nn.init_params(jax.random.PRNGKey(0), specificity_specs(cfg)), cfg)
    texts["jit_specificity_apply"] = model._apply.lower(
        model.params, preds).as_text()
    for name, text in texts.items():
        assert text.startswith(f"module @{name} "), text[:80]
