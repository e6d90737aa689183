"""Float64 reference scan and the comparison that decides ``correct``.

The reference is a plain full scan of the benchmark's own catalog on the
host (after ``chip_smoke.py``'s float64 ``Reference``): for a predicate
embedding and a threshold, the rows whose float64 cosine distance
``1 - p.x`` is at most the threshold. It imports nothing of the program.

What is compared, per sampled filter of a plan the window served: the
plan's selectivity times N (its count) at the estimate's threshold, as the
served probe saw it (float32), against

* ``exact``: rows with float64 distance <= threshold, and
* the band ``[#(d < thr - BAND), #(d <= thr + BAND)]``: the program's
  stated guarantee is that its counts are exact except for rows within
  ``BAND`` of a threshold, which float32 scoring may put either side.

``control_counts`` is the same scan put in the program's place at a
lower precision (bfloat16 operands, one or three MXU passes), on the
device: the control that the comparison must fail.
"""

from __future__ import annotations

import numpy as np

BAND = 1e-4           # the program's stated count guarantee (PERF.md §2)
CHUNK = 65536         # rows per float64 block


class Reference:
    """Float64 counts over ``images`` for batches of predicates.

    Each block of rows is scored in float32 first; a row whose float32
    distance lies more than ``MARGIN`` from every cut (``thr - BAND``,
    ``thr``, ``thr + BAND``) falls on the same side of it in float64,
    since a float32 dot of two unit vectors of width d is off by at most
    d * 2**-24 (6.9e-5 at d = 1152). The rows within ``MARGIN`` of a cut
    are scored again in float64, and only they decide near the cuts."""

    MARGIN = 2e-4

    def __init__(self, images: np.ndarray):
        self.images = images
        if images.shape[1] * 2.0 ** -24 >= self.MARGIN / 2:
            raise ValueError(f"width {images.shape[1]} is too wide for the "
                             f"float32 pre-pass margin {self.MARGIN}")

    def counts(self, preds: np.ndarray, thr: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(exact, lo, hi), each (P,) int64, for preds (P, d) and float32
        thresholds (P,)."""
        p32 = np.asarray(preds, np.float32)
        p64 = p32.astype(np.float64)
        t = np.asarray(thr, np.float32).astype(np.float64)
        cuts = (t - BAND, t, t + BAND)
        exact = np.zeros(len(p64), np.int64)
        lo = np.zeros(len(p64), np.int64)
        hi = np.zeros(len(p64), np.int64)
        for s in range(0, len(self.images), CHUNK):
            x = self.images[s:s + CHUNK]
            d32 = (1.0 - x @ p32.T).astype(np.float64)        # (rows, P)
            near = np.zeros(d32.shape, bool)
            for c in cuts:
                near |= np.abs(d32 - c[None, :]) <= self.MARGIN
            far = ~near
            exact += ((d32 <= t) & far).sum(axis=0)
            lo += ((d32 < t - BAND) & far).sum(axis=0)
            hi += ((d32 <= t + BAND) & far).sum(axis=0)
            r, c = np.nonzero(near)
            d64 = 1.0 - np.einsum("ij,ij->i", x[r].astype(np.float64),
                                  p64[c])
            exact += np.bincount(c, d64 <= t[c], len(t)).astype(np.int64)
            lo += np.bincount(c, d64 < t[c] - BAND, len(t)).astype(np.int64)
            hi += np.bincount(c, d64 <= t[c] + BAND, len(t)).astype(np.int64)
        return exact, lo, hi


def compare(counts: np.ndarray, exact: np.ndarray, lo: np.ndarray,
            hi: np.ndarray) -> dict:
    """The numbers ``correct`` is decided on, for one run's sample:
    ``band_misses`` (counts outside the band), ``count_gap_max`` (the
    widest |count - exact| of a sampled filter) and ``count_gap_sum``
    (|count - exact| summed over the sample)."""
    counts = np.asarray(counts, np.int64)
    gap = np.abs(counts - np.asarray(exact, np.int64))
    return {
        "band_misses": int(((counts < lo) | (counts > hi)).sum()),
        "count_gap_max": int(gap.max()) if len(gap) else 0,
        "count_gap_sum": int(gap.sum()),
    }


def control_counts(images_dev, preds: np.ndarray, thr: np.ndarray,
                   passes: int) -> np.ndarray:
    """Counts from the scan at a lower precision than the program's f32:
    operands rounded to bfloat16 with f32 accumulation, ``passes`` = 1
    (what an MXU does with f32 at the default precision) or 3 (hi*hi +
    hi*lo + lo*hi, the ``HIGH`` precision). The rounding is explicit
    (``reduce_precision``), so the control means the same on every
    backend. Runs on the device that holds ``images_dev``."""
    import jax
    import jax.numpy as jnp

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    @jax.jit
    def scan(x, p, t):
        xh, ph = bf16(x), bf16(p)
        dot = lambda a, b: jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST)
        sims = dot(xh, ph)
        if passes == 3:
            sims = sims + dot(xh, bf16(p - ph)) + dot(bf16(x - xh), ph)
        return ((1.0 - sims) <= t[None, :]).sum(axis=0)

    out = []
    for s in range(0, images_dev.shape[0], 1 << 18):
        out.append(np.asarray(scan(images_dev[s:s + (1 << 18)],
                                   jnp.asarray(preds, jnp.float32),
                                   jnp.asarray(thr, jnp.float32))))
    return np.sum(out, axis=0).astype(np.int64)
