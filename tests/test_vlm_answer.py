"""The oracle VLM's truth lookup: the sorted-match-list path and the N-row
mask path give the bits the mask alone gave, and the estimators that call
it calibrate to the same thresholds."""

import dataclasses
import types

import numpy as np
import pytest

from repro.core import synthetic
from repro.core.estimators import (
    EnsembleEstimator,
    KVBatchEstimator,
    SpecificityEstimator,
)
from repro.core.synthetic import Concept, make_corpus

N_ROWS = 20_000          # sorted path up to 39 ids, mask path from 40
EMPTY_NODE = 10_000


def _mask_vlm_answer(self, node_id, image_ids, seed=0):
    """``Corpus.vlm_answer`` as it was before the sorted lookup: the
    reference both paths are held to."""
    truth = np.zeros(len(self.images), bool)
    truth[self.true_matches(node_id)] = True
    ans = truth[image_ids]
    g = np.random.default_rng(node_id * 104729 + seed)
    u = g.random(len(image_ids))
    fn = ans & (u < self.vlm_error)
    fp = (~ans) & (u < self.vlm_error / 8.0)
    return np.where(fn, False, np.where(fp, True, ans))


@pytest.fixture(scope="module")
def corpus():
    c = make_corpus("wildlife", n_images=N_ROWS, dim=32, seed=5)
    empty = Concept(EMPTY_NODE, 1, 0, [], c.concepts[0].direction, "empty",
                    np.empty(0, np.int64))
    return dataclasses.replace(c, concepts={**c.concepts, EMPTY_NODE: empty})


def _ids(kind, k, rng):
    if kind == "random":
        return rng.integers(0, N_ROWS, k)
    if kind == "unsorted":
        return np.sort(rng.choice(N_ROWS, k, replace=False))[::-1].copy()
    if kind == "duplicated":
        return np.repeat(rng.integers(0, N_ROWS, (k + 1) // 2), 2)[:k]
    if kind == "first_last":
        return np.resize(np.array([0, N_ROWS - 1]), k)
    return rng.choice(N_ROWS, k, replace=False).astype(np.int32)   # int32


@pytest.mark.parametrize("k", [2, 32, 39, 40, 5000], ids=lambda k: f"k{k}")
@pytest.mark.parametrize("kind", ["random", "unsorted", "duplicated",
                                  "first_last", "int32"])
def test_vlm_answer_matches_mask_on_every_node(corpus, kind, k):
    rng = np.random.default_rng(k)
    ids = _ids(kind, k, rng)
    assert len(ids) == k
    for nid in corpus.concepts:
        for seed in (0, 7):
            got = corpus.vlm_answer(nid, ids, seed=seed)
            want = _mask_vlm_answer(corpus, nid, ids, seed=seed)
            assert got.dtype == want.dtype == bool
            np.testing.assert_array_equal(got, want, err_msg=f"node {nid}")


@pytest.mark.parametrize("n_ids", [0, 32, 5000])
def test_vlm_answer_empty_match_list_and_empty_ids(corpus, n_ids):
    ids = np.arange(n_ids, dtype=np.int64)
    got = corpus.vlm_answer(EMPTY_NODE, ids, seed=3)
    np.testing.assert_array_equal(
        got, _mask_vlm_answer(corpus, EMPTY_NODE, ids, seed=3))
    for nid in (0, EMPTY_NODE):
        assert corpus.vlm_answer(nid, ids[:0]).shape == (0,)


@pytest.mark.parametrize("n_ids,sorted_path", [(32, True), (2048, True),
                                               (2049, False)])
def test_served_calibration_takes_the_sorted_path(monkeypatch, n_ids,
                                                  sorted_path):
    """32 sample ids against 2**20 rows (a served kv-batch calibration)
    search the match list and build no N-row mask."""
    n = 1 << 20
    c = synthetic.Corpus(
        name="t", dim=1, images=np.empty((n, 1), np.float32),
        image_leaf=np.zeros(n, np.int64),
        concepts={0: Concept(0, 0, None, [], np.ones(1), "root",
                             np.arange(n // 3, n, 2, dtype=np.int64))},
        text_noise=0.0, vlm_error=0.08, rng=np.random.default_rng(0))
    calls = []
    real = np.searchsorted
    monkeypatch.setattr(np, "searchsorted",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    ids = np.random.default_rng(1).integers(0, n, n_ids)
    got = c.vlm_answer(0, ids, seed=2)
    assert bool(calls) == sorted_path
    np.testing.assert_array_equal(got, _mask_vlm_answer(c, 0, ids, seed=2))


def _estimators(corpus):
    ids = np.sort(np.random.default_rng(11).choice(N_ROWS, 32,
                                                   replace=False))
    store = types.SimpleNamespace(sample_ids=ids.astype(np.int32))
    kvb = KVBatchEstimator(corpus, None, store, run_machinery=False)
    anchor = corpus.concepts[0].direction.astype(np.float32)
    model = types.SimpleNamespace(
        thresholds=lambda embs: (1.0 - embs @ anchor).astype(np.float32))
    spec = SpecificityEstimator(corpus, None, model)
    return kvb, EnsembleEstimator(spec, kvb)


def _probe(corpus):
    def selectivity_batch(embs, thrs):
        d = 1.0 - corpus.images @ np.asarray(embs).T
        return (d <= np.asarray(thrs)).mean(axis=0)
    return selectivity_batch


@pytest.mark.parametrize("which", ["kvbatch", "ensemble"])
def test_estimate_batch_bitwise_as_with_the_mask(corpus, monkeypatch, which):
    nodes = sorted(corpus.concepts)
    batches = [nodes[i:i + 4] for i in range(0, len(nodes), 4)]
    assert 32 * 512 <= N_ROWS       # the calibration takes the sorted path

    def run():
        kvb, ens = _estimators(corpus)
        est = kvb if which == "kvbatch" else ens
        return [[(e.threshold, e.extra["sample_matches"], e.selectivity)
                 for e in est.estimate_batch(b, seed=s, probe=_probe(corpus))]
                for s in (0, 9) for b in batches]

    new = run()
    monkeypatch.setattr(synthetic.Corpus, "vlm_answer", _mask_vlm_answer)
    old = run()
    assert new == old
    assert any(m > 0 for batch in new for _, m, _ in batch)


def test_leaf_image_ids_sorted_make_corpus(corpus):
    for c in corpus.concepts.values():
        ids = c.leaf_image_ids
        assert ids.dtype == np.int64
        assert (np.diff(ids) > 0).all(), c.node_id


def test_leaf_image_ids_sorted_bench_catalog():
    from bench.catalog import as_corpus, make_catalog

    cat = make_catalog("ecommerce", rows=4096, dim=32, seed=1)
    corpus = as_corpus(cat)
    assert len(corpus.concepts[0].leaf_image_ids) == 4096
    for c in corpus.concepts.values():
        assert (np.diff(c.leaf_image_ids) > 0).all(), c.node_id
