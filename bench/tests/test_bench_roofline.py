"""The probe kernel's work function and the peaks table."""

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench.roofline import least_time, peaks, probe_work

V5E = "TPU v5 lite"


def test_full_scan_work_at_serving_width():
    nbytes, flops = probe_work(1 << 20, 1152, 4, 64)
    assert nbytes == (1 << 20) * 1152 * 4 + 64 * 1152 * 4
    assert flops == 2 * (1 << 20) * 1152 * 64


@pytest.mark.parametrize("b", [1, 8, 64])
def test_bytes_bind_at_f32_up_to_64_predicates(b):
    nbytes, flops = probe_work(1 << 20, 1152, 4, b)
    t, bound = least_time(nbytes, flops, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / 819e9)
    # 4.83 GB at 819 GB/s
    assert 5.8e-3 < t < 6.0e-3


def test_flops_bind_past_the_ridge():
    nbytes, flops = probe_work(1 << 16, 1152, 2, 4096)
    t, bound = least_time(nbytes, flops, V5E)
    assert bound == "flops"
    assert t == pytest.approx(flops / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks("TPU v99")
    with pytest.raises(KeyError):
        least_time(1.0, 1.0, "cpu")
