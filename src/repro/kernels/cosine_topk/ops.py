"""Jitted wrappers: pad to TPU tiles, run the kernel, merge block partials.

``cosine_probe`` is the scalar (one-predicate) path; ``cosine_probe_batch``
scores a whole (B, d) predicate batch in one store pass via the MXU kernel.
Both clamp k to N and handle non-tile-aligned N and d by padding (padded
rows are masked to +inf distance inside the kernel, so counts and top-k are
exact).

B-tiled dispatch: when the predicate batch outgrows ``block_b`` (coalesced
serving batches — many concurrent queries' filters merged into one probe),
``cosine_probe_batch`` pads B up to a multiple of ``block_b`` and routes to
the 2-D-grid tiled kernel so the resident (d, B) panel never exceeds the
VMEM budget (see kernel.py). Pass ``tiled=True``/``False`` to force either
path — parity between the two is tested for B below, at, and above the
tile size. Padded predicate columns are zero vectors whose outputs are
sliced off before the merge, so results are exact.

``cosine_probe_batch_masked`` scores only a *runtime-length* row prefix
(the valid count travels as an SMEM scalar, not a trace constant) — the
entry point for the cluster-pruned index's boundary-subset scans, where the
subset length changes every probe but the padded bucket shape does not.

``cosine_probe_rowmask`` / ``cosine_probe_batch_rowmask`` score an
*arbitrarily-masked* row set (per-row int32 validity vector, dead rows ->
+inf) — the entry points for the mutable store's hot-tail and tombstone
scans, where live rows are not a prefix. The mask is padded with zeros to
the same bucket as the store, so padding never scores.

Shape rules: ``block_n=None`` picks the store block from d and the dtype
(``kernel.block_rows``). The kernel selects each block's top-k in VMEM for
k <= ``kernel.MAX_SELECT_K``; a larger k (threshold calibration) makes it
write every block's whole distance tile, and the merge below sorts them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.cosine_topk.kernel import (
    MAX_SELECT_K,
    block_rows,
    probe_blocks,
)

f32 = jnp.float32


def _pad_to(x, m, axis, value=0.0):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _probe(store, preds, thresholds, valid, *, k, block_n, block_b, tiled,
           rows, scalar, interpret):
    """Shared body: preds (B, d), thresholds (B, T) -> (counts (B, T),
    k smallest distances (B, k) ascending)."""
    n, d = store.shape
    b = preds.shape[0]
    k = min(k, n)
    if block_n is None:
        block_n = block_rows(-(-d // 128) * 128, store.dtype.itemsize)
    block_n = min(block_n, max(128, 1 << (n - 1).bit_length()))
    kk = min(max(k, 1), block_n)
    if kk > MAX_SELECT_K:
        kk = block_n                  # whole tiles; the merge sorts them
    sp = _pad_to(_pad_to(store, 128, 1), block_n, 0)
    thr = thresholds.astype(f32)
    preds = preds.astype(store.dtype)
    if rows == "prefix":
        valid = jnp.asarray(valid, jnp.int32).reshape(1, 1)
    elif rows == "mask":
        valid = _pad_to(valid.astype(jnp.int32), block_n, 0)[None, :]
    bb = None
    if not scalar and (b > block_b if tiled is None else tiled):
        # pad the predicate axis to a tile multiple; zero columns are
        # scored but sliced off below, so padding never changes results
        bb = min(block_b, max(8, 1 << (b - 1).bit_length()))
        preds, thr = _pad_to(preds, bb, 0), _pad_to(thr, bb, 0)
    pp = _pad_to(preds, 128, 1)
    counts_b, topk_b = probe_blocks(
        sp, pp if scalar else pp.T, thr, valid, k=kk, block_n=block_n,
        block_b=bb, rows=rows, scalar=scalar, n_total=n,
        interpret=interpret)
    counts = counts_b[:, :b].sum(axis=0)                    # (B, T)
    # (nblocks, B, kk) -> (B, nblocks*kk) -> per-predicate global top-k
    flat = topk_b[:, :b].transpose(1, 0, 2).reshape(b, -1)
    return counts, -jax.lax.top_k(-flat, k)[0]


_STATIC = ("k", "block_n", "interpret")
_STATIC_BATCH = ("k", "block_n", "block_b", "tiled", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def cosine_probe(
    store: jax.Array,        # (N, d)
    pred: jax.Array,         # (d,)
    thresholds: jax.Array,   # (T,)
    *,
    k: int = 128,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused probe: (counts (T,) int32, k smallest distances (k,) ascending)."""
    counts, top = _probe(store, pred[None, :], thresholds[None, :], None,
                         k=k, block_n=block_n, block_b=None, tiled=False,
                         rows="static", scalar=True, interpret=interpret)
    return counts[0], top[0]


@functools.partial(jax.jit, static_argnames=_STATIC_BATCH)
def cosine_probe_batch(
    store: jax.Array,        # (N, d)
    preds: jax.Array,        # (B, d) predicate batch
    thresholds: jax.Array,   # (B, T) per-predicate threshold vectors
    *,
    k: int = 128,
    block_n: int | None = None,
    block_b: int = 128,
    tiled: bool | None = None,  # None = auto (tile when B > block_b)
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched fused probe — one store pass for B predicates.

    Dispatch: B <= ``block_b`` keeps the whole (d, B) panel resident
    (single-grid kernel); larger B goes through the B-tiled kernel so VMEM
    use is bounded by ``block_b`` per step. Force with ``tiled``.

    Returns (counts (B, T) int32, k smallest distances (B, k) ascending).
    """
    return _probe(store, preds, thresholds, None, k=k, block_n=block_n,
                  block_b=block_b, tiled=tiled, rows="static", scalar=False,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def cosine_probe_masked(
    store: jax.Array,        # (M, d) scan buffer; rows >= n_valid are dead
    n_valid: jax.Array,      # int32 scalar — live row-prefix length
    pred: jax.Array,         # (d,)
    thresholds: jax.Array,   # (T,)
    *,
    k: int = 128,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Scalar probe over the first ``n_valid`` rows of ``store``.

    One-predicate twin of ``cosine_probe_batch_masked`` using the scalar
    kernel's VPU reduce, so a pruned scan's distances are bitwise the full
    ``cosine_probe`` scan's. Returns (counts (T,), top-k (k,) ascending).
    """
    counts, top = _probe(store, pred[None, :], thresholds[None, :], n_valid,
                         k=k, block_n=block_n, block_b=None, tiled=False,
                         rows="prefix", scalar=True, interpret=interpret)
    return counts[0], top[0]


@functools.partial(jax.jit, static_argnames=_STATIC_BATCH)
def cosine_probe_batch_masked(
    store: jax.Array,        # (M, d) scan buffer; rows >= n_valid are dead
    n_valid: jax.Array,      # int32 scalar — live row-prefix length
    preds: jax.Array,        # (B, d) predicate batch
    thresholds: jax.Array,   # (B, T) per-predicate threshold vectors
    *,
    k: int = 128,
    block_n: int | None = None,
    block_b: int = 128,
    tiled: bool | None = None,  # None = auto (tile when B > block_b)
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched probe over the first ``n_valid`` rows of ``store``.

    The cluster-pruned index pads its boundary-union scan buffer to a
    power-of-two bucket and masks the tail here, so the kernel compiles one
    trace per bucket shape instead of one per subset length. Dead rows are
    +inf distance inside the kernel — counts and top-k are exact over the
    valid prefix (top-k entries past ``n_valid`` come back +inf).

    B-tiled dispatch mirrors ``cosine_probe_batch``: coalesced pruned
    batches with B > ``block_b`` route through the 2-D-grid masked kernel
    so the resident predicate panel stays inside the VMEM budget; padded
    predicate columns are sliced off before the merge.

    Returns (counts (B, T) int32, k smallest distances (B, k) ascending).
    """
    return _probe(store, preds, thresholds, n_valid, k=k, block_n=block_n,
                  block_b=block_b, tiled=tiled, rows="prefix", scalar=False,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def cosine_probe_rowmask(
    store: jax.Array,        # (M, d) scan buffer
    mask: jax.Array,         # (M,) — nonzero = live row; 0 = tombstone
    pred: jax.Array,         # (d,)
    thresholds: jax.Array,   # (T,)
    *,
    k: int = 128,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Scalar probe over the live (mask != 0) rows of ``store``.

    The mutable store's hot-tail / tombstone scan: live rows form an
    arbitrary pattern, not a prefix. Uses the scalar kernel's VPU reduce so
    a masked scan's per-row distances are bitwise the full scalar scan's.
    Returns (counts (T,), top-k (k,) ascending; dead slots come back +inf).
    """
    counts, top = _probe(store, pred[None, :], thresholds[None, :], mask,
                         k=k, block_n=block_n, block_b=None, tiled=False,
                         rows="mask", scalar=True, interpret=interpret)
    return counts[0], top[0]


@functools.partial(jax.jit, static_argnames=_STATIC_BATCH)
def cosine_probe_batch_rowmask(
    store: jax.Array,        # (M, d) scan buffer
    mask: jax.Array,         # (M,) — nonzero = live row; 0 = tombstone
    preds: jax.Array,        # (B, d) predicate batch
    thresholds: jax.Array,   # (B, T) per-predicate threshold vectors
    *,
    k: int = 128,
    block_n: int | None = None,
    block_b: int = 128,
    tiled: bool | None = None,  # None = auto (tile when B > block_b)
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched probe over the live (mask != 0) rows of ``store``.

    Batched twin of ``cosine_probe_rowmask`` (MXU matmul, same reduction
    order as ``cosine_probe_batch`` so masked per-row distances are bitwise
    the full batched scan's). B-tiled dispatch mirrors
    ``cosine_probe_batch``; the mask restreams with the store blocks, so
    tiling never changes which rows are live.

    Returns (counts (B, T) int32, k smallest distances (B, k) ascending).
    """
    return _probe(store, preds, thresholds, mask, k=k, block_n=block_n,
                  block_b=block_b, tiled=tiled, rows="mask", scalar=False,
                  interpret=interpret)
