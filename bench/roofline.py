"""Chip peaks and the probe kernel's work function.

The peaks are copied from ``src/repro/analysis/roofline.py`` (``PEAKS``),
keyed by the ``device_kind`` JAX reports. A kind that is not in the table
raises: a roofline share against a guessed peak is no measurement.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # FLOP/s (bf16 MXU)
    hbm_bw: float       # HBM bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None


def probe_work(rows: int, d: int, itemsize: int, b: int
               ) -> tuple[float, float]:
    """(bytes, FLOPs) one probe launch needs: the ``rows`` scanned store
    rows of ``d`` values of ``itemsize`` bytes read once, the (b, d) f32
    predicate panel, and one multiply-add per row, dimension and
    predicate."""
    nbytes = rows * d * itemsize + b * d * 4
    flops = 2.0 * rows * d * b
    return float(nbytes), flops


def least_time(nbytes: float, flops: float, device_kind: str
               ) -> tuple[float, str]:
    """(seconds, the bound that binds: "bytes" or "flops")."""
    pk = peaks(device_kind)
    t_bytes, t_flops = nbytes / pk.hbm_bw, flops / pk.flops
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
