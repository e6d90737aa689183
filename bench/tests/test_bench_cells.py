"""A tiny-size CPU rehearsal of both cells: set-up, the closed-loop
window through the timed handle, metric readers, and the comparison with
the float64 reference, through the harness's own functions."""

import pytest

from bench_tiny import CELL1, CELL2, SEED, tiny_root, tiny_scale
from bench import harness


@pytest.mark.parametrize("workload,trace", [(CELL1, False), (CELL2, True)])
def test_cell_rehearsal(tmp_path, workload, trace):
    import jax

    root = tiny_root(tmp_path)
    out = harness.run(workload, SEED, 1.0, trace, root=root,
                      scale=tiny_scale(workload), devs=jax.devices(),
                      trace_dir=tmp_path / "trace")
    info = out.pop("_info")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert info["compared"] > 0
    names = {m["name"] for m in harness.metrics_for(
        harness.load_manifest(root), workload, trace)}
    if trace:
        # no device on the CPU: the kernel readers find nothing to read
        assert set(out["metrics"]) == names - {"kernel_ms", "kernel_roofline"}
        assert 0 < out["metrics"]["scan_fraction"]["value"] <= 100
        assert out["device"]["window_s"] > 0
        assert "breakdown" in out
    else:
        assert set(out["metrics"]) == names
        assert out["metrics"]["plans_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
