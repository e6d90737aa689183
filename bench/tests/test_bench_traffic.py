"""Traffic mixes are data: a new file is found by its name, no edit."""

import json

import numpy as np

from bench_tiny import CELL1, SEED, tiny_root
from bench import harness
from bench.catalog import make_catalog
from bench.traffic import WINDOW, QueryStream, filter_pool, load_mix


def test_dropped_in_mix_is_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    mix = {"name": "burst-c3", "sessions": 3, "filters": [1, 2],
           "pool": "leaves", "repeat_share": 0.5, "repeat_lag_max": 4,
           "warmup_plans": 4, "sample_filters": 8}
    (root / "bench" / "traffic" / "burst-c3.json").write_text(json.dumps(mix))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "wildlife-1m.burst-c3",
                             "config": "wildlife-1m", "traffic": "burst-c3",
                             "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = harness.Cell(root, "wildlife-1m.burst-c3", SEED,
                        scale={"rows": 512, "d": 16})
    assert cell.mix == mix
    cat = make_catalog("wildlife", 512, 16, SEED)
    stream = QueryStream(cell.mix, filter_pool(cell.mix, cat), SEED, WINDOW)
    qs = [stream.get(i) for i in range(64)]
    assert all(1 <= len(q.nodes) <= 2 for q in qs)
    assert all(set(q.nodes) <= set(cat.leaves) for q in qs)
    assert any(q.repeat_of >= 0 for q in qs)


def test_streams_are_a_function_of_the_seed():
    mix = load_mix(harness.ROOT, "miss-c64")
    cat = make_catalog("wildlife", 256, 16, SEED)
    pool = filter_pool(mix, cat)
    a = QueryStream(mix, pool, SEED, WINDOW)
    b = QueryStream(mix, pool, SEED, WINDOW)
    assert [a.get(i) for i in range(50)] == [b.get(i) for i in range(50)]
    assert [a.get(i) for i in range(50)] != [
        QueryStream(mix, pool, SEED + 1, WINDOW).get(i) for i in range(50)]
    for q in (a.get(i) for i in range(200)):
        assert 2 <= len(q.nodes) <= 4 and len(set(q.nodes)) == len(q.nodes)
        if q.repeat_of >= 0:
            src = a.get(q.repeat_of)
            assert (src.nodes, src.paraphrase) == (q.nodes, q.paraphrase)


def test_catalog_is_a_function_of_the_seed():
    a = make_catalog("ecommerce", 1024, 32, SEED)
    b = make_catalog("ecommerce", 1024, 32, SEED)
    np.testing.assert_array_equal(a.images, b.images)
    np.testing.assert_allclose(np.linalg.norm(a.images, axis=1), 1.0,
                               atol=1e-5)
    assert not np.array_equal(a.images,
                              make_catalog("ecommerce", 1024, 32, 5).images)
    assert a.counts.sum() == 1024
