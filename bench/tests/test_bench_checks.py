"""The comparison that decides ``correct`` fails what it must.

One tiny cell is built on the CPU. Sound windows come out correct. The
control (the reference scan put in the program's place in bfloat16) and
each fault planted underneath the timed path come out not correct:
an answer altered where it is produced (counts handed to the wrong
predicate of the batch), half the store left out and the count of the
rest doubled, half of a batch's predicates answered with the mean of the
rest, and a probe that returns its previous answer unchanged.
"""

import itertools

import numpy as np
import pytest

from bench_tiny import CELL1, SEED, TINY_LIMITS, tiny_root, tiny_scale
from bench import harness
from bench.reference import compare, control_counts
from bench.traffic import QueryStream

_stream_ids = itertools.count(100)


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("tiny"))
    c = harness.Cell(root, CELL1, SEED, scale=tiny_scale(CELL1))
    c.build()
    c.warm_up()
    yield c
    c.close()


def _window(cell, seconds=0.6):
    stream = QueryStream(cell.mix, cell.pool, SEED, next(_stream_ids))
    recs, _, _ = harness.serve_loop(cell.handle, cell.estimator, stream,
                                    sessions=cell.mix["sessions"],
                                    seconds=seconds)
    failed = sum(r.error is not None for r in recs)
    sample = harness.sample_filters(recs, cell.mix["sample_filters"], SEED)
    checks, info = harness.check(cell.catalog, sample, TINY_LIMITS, failed,
                                 cell.hist.n)
    return all(c["value"] <= c["limit"] for c in checks.values()), info


def test_sound_window_is_correct(cell):
    ok, info = _window(cell)
    assert ok and info["compared"] == cell.mix["sample_filters"]


def test_bf16_control_is_not_correct(cell):
    import jax

    _, info = _window(cell)
    cc = control_counts(jax.device_put(cell.catalog.images), info["preds"],
                        info["thr"], passes=1)
    nums = compare(cc, info["exact"], info["lo"], info["hi"])
    assert nums["count_gap_max"] > TINY_LIMITS["count_gap_max"]


def _rotate(counts, topk, hist, preds, thr):
    return np.roll(counts, 1, axis=0), topk


def _half_rows(counts, topk, hist, preds, thr):
    half = np.asarray(hist.embeddings)[:hist.n // 2]
    d = 1.0 - half @ np.asarray(preds, np.float32).T
    return 2 * (d <= np.asarray(thr, np.float32).reshape(1, -1)).sum(
        axis=0)[:, None].astype(np.int32), topk


def _half_batch(counts, topk, hist, preds, thr):
    counts = counts.copy()
    b = len(counts)
    if b > 1:
        counts[b // 2:] = int(counts[:b // 2].mean())
    return counts, topk


class _Stale:
    def __init__(self):
        self.last = {}

    def __call__(self, counts, topk, hist, preds, thr):
        prev = self.last.get(counts.shape)
        self.last[counts.shape] = counts
        return (counts if prev is None else prev), topk


@pytest.mark.parametrize("fault", [_rotate, _half_rows, _half_batch,
                                   _Stale()],
                         ids=["answer-altered", "half-rows", "half-batch",
                              "state-unchanged"])
def test_fault_under_the_timed_path_is_not_correct(cell, fault,
                                                   monkeypatch):
    real = type(cell.hist).probe_batch

    def broken(self, preds, thresholds, **kw):
        counts, topk = real(self, preds, thresholds, **kw)
        return fault(np.asarray(counts), np.asarray(topk), self, preds,
                     thresholds)

    monkeypatch.setattr(type(cell.hist), "probe_batch", broken)
    ok, _ = _window(cell)
    assert not ok
