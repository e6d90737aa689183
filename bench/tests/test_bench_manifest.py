"""BENCHMARK.json and the files it names agree."""

import json
import re

import pytest

from bench_tiny import ROOT
from bench import harness

MANIFEST = harness.load_manifest(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


@pytest.mark.parametrize("entry", METRICS, ids=[m["name"] for m in METRICS])
def test_each_metric_has_a_reader_that_agrees(entry):
    reader = harness.load_reader(ROOT, entry["name"])
    assert NAME.match(entry["name"])
    assert reader.UNIT == entry["unit"]
    if entry in MANIFEST["per_layer"]:
        assert (reader.LAYER, reader.MOVES) == (entry["layer"],
                                                 entry["moves"])
        # every cell it lists reports the end-to-end metric it moves
        for cell in entry["workloads"]:
            names = {m["name"] for m in harness.metrics_for(
                MANIFEST, cell, trace=False)}
            assert entry["moves"] in names


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=[w["name"] for w in MANIFEST["workloads"]])
def test_each_cell_finds_its_files(cell):
    cfg = harness.load_config(ROOT, MANIFEST, cell["config"])
    assert cfg["name"] == cell["config"]
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    assert mix["name"] == cell["traffic"]
    limits = harness.load_limits(ROOT, cell["name"])
    assert set(limits) == {"failed_plans", "band_misses", "count_gap_max",
                           "count_gap_sum"}
    e2e = {m["name"] for m in harness.metrics_for(MANIFEST, cell["name"],
                                                  trace=False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(MANIFEST, cell["name"], trace=True)


def test_bounds_and_paths_keep_to_the_contract():
    assert MANIFEST["paths"] == ["bench"]
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in MANIFEST["configs"]:
        assert c["file"].startswith("bench/")
        assert set(c["reduced"]) <= set(json.loads(
            (ROOT / c["file"]).read_text()))
