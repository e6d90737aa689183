"""Fused cosine-distance probe kernel: counts-under-thresholds + block top-k.

The Semantic Histogram's online hot path (paper §2.2 step 5): one pass over
the (N, d) embedding store. Bandwidth-bound by design — the kernel streams
N-blocks of the store HBM->VMEM and reduces counts + a per-block top-k in
VMEM; distances never return to HBM.

One ``pallas_call`` builder (``probe_blocks``) covers every probe the
wrappers in ``ops.py`` expose, along two static axes:

  * ``scalar`` — one predicate scored by a VPU broadcast-reduce over d
    (``(block_n, d) * (1, d)``), or B predicates scored by one MXU matmul
    ``(block_n, d) x (d, B)`` per store block. The batched form streams the
    store HBM->VMEM **once** for the whole predicate batch, so probe HBM
    traffic drops ~B× versus B scalar probes.
  * ``rows`` — which store rows are live:
      ``"static"``  rows < ``n_total`` (a trace constant: tail padding);
      ``"prefix"``  rows < a runtime SMEM scalar — the cluster-pruned index
                    (``repro.index``) gathers boundary segments into a
                    power-of-two bucket whose valid prefix changes every
                    probe, so one compile serves every subset length;
      ``"mask"``    a per-row int32 vector streamed with the store blocks —
                    the mutable store's tombstones and hot-tail slots are
                    live/dead in patterns a prefix cannot express.
    Dead rows score +inf: never counted, never in the top-k.

Grid: (N / block_n, B_pad / block_b). The predicate axis is the *minor*
grid dimension, so the (block_n, d) store block index is constant across
the inner loop and Pallas fetches each store block from HBM once; only the
small (d, block_b) panel restreams. ``block_b = B`` gives the untiled probe.
Outputs are per-block partials merged by ops.py (O(nblocks * B * k)).

Per-block top-k: the chip's Pallas lowering has no ``top_k`` or ``sort``,
so the kernel selects the k smallest distances with k rounds of
min-and-mask over the (B, block_n) tile (``_block_smallest``). Rounds cost
a full tile pass each, so in-kernel selection is capped at
``MAX_SELECT_K``; for larger k (threshold calibration) ops.py asks for the
whole tile instead (``k == block_n``), which the kernel writes unselected
and the XLA merge sorts.

Numerics: the MXU contraction runs at ``Precision.HIGHEST`` (f32 passes).
At the default precision the chip rounds f32 operands to bf16, moving a
cosine distance by ~1e-3 — far above the index's ``eps=1e-4`` bound slack,
and enough to move a row across a threshold.

TPU tiling / VMEM: block_n is a multiple of 128 (lanes), d is padded to a
multiple of 128 by ops.py, and every output block's last two dims either
equal the array's or are (8, 128)-aligned — hence the (nblocks, 1, T)
scalar counts and the (1, N_pad) mask row. Pallas double-buffers the store
block, so it holds 2 * block_n * d * itemsize bytes of VMEM (18.9 MB for an
f32 2048 x 1152 block). The compiler's default *scoped* VMEM limit is
16 MiB (v5e has 128 MiB of VMEM in all); ops.py picks block_n from d and
the dtype so that the store buffers fit ``STORE_VMEM_BYTES`` under that
default — 1024 rows at d=1152 f32. Raising the limit would buy nothing:
a 4.7 MB block already takes ~6 µs to stream at 819 GB/s, far above the
per-step overhead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

f32 = jnp.float32

MAX_SELECT_K = 128                 # largest k selected inside the kernel
STORE_VMEM_BYTES = 10 * 1024 ** 2  # double-buffered store block budget


def block_rows(d_pad: int, itemsize: int) -> int:
    """Largest power-of-two store block (>= 128 rows, <= 2048) whose
    double buffer fits ``STORE_VMEM_BYTES``."""
    rows = 2048
    while rows > 128 and 2 * rows * d_pad * itemsize > STORE_VMEM_BYTES:
        rows //= 2
    return rows


def _block_smallest(db: jax.Array, k: int) -> jax.Array:
    """(R, L) -> (R, k): the k smallest per row, ascending.

    k rounds of min-and-mask: each round takes the row minimum and retires
    its first occurrence (ties retire one lane per round, so duplicated
    distances are kept with their multiplicity). ``k == L`` returns the
    tile itself — the merge in ops.py sorts it."""
    r, n = db.shape
    if k == n:
        return db
    if k == 1:
        return jnp.min(db, axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)

    def one_round(i, carry):
        d, out = carry
        m = jnp.min(d, axis=1, keepdims=True)                     # (R, 1)
        first = jnp.min(jnp.where(d == m, lane, n), axis=1, keepdims=True)
        return (jnp.where(lane == first, jnp.inf, d),
                jnp.where(col == i, m, out))

    _, out = jax.lax.fori_loop(
        0, k, one_round, (db, jnp.full((r, k), jnp.inf, f32)))
    return out


def _probe_kernel(*refs, k: int, block_n: int, rows: str, scalar: bool,
                  n_total: int):
    if rows == "prefix":
        nv_ref, store_ref, preds_ref, thr_ref, counts_ref, topk_ref = refs
    elif rows == "mask":
        store_ref, mask_ref, preds_ref, thr_ref, counts_ref, topk_ref = refs
    else:
        store_ref, preds_ref, thr_ref, counts_ref, topk_ref = refs
    block = store_ref[...].astype(f32)                    # (block_n, d)
    if scalar:
        # VPU broadcast-reduce: exact f32 products and sums
        pred = preds_ref[...].astype(f32)                 # (1, d)
        db = (1.0 - jnp.sum(block * pred, axis=-1))[None, :]   # (1, block_n)
    else:
        preds = preds_ref[...].astype(f32)                # (d, B)
        sims = jnp.dot(block, preds, preferred_element_type=f32,
                       precision=jax.lax.Precision.HIGHEST)   # (block_n, B)
        db = (1.0 - sims).T                               # (B, block_n)

    if rows == "mask":
        live = mask_ref[...] != 0                         # (1, block_n)
    else:
        row = (pl.program_id(0) * block_n
               + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1))
        live = row < (nv_ref[0, 0] if rows == "prefix" else n_total)
    db = jnp.where(live, db, jnp.inf)

    thr = thr_ref[...]                                    # (B, T)
    counts_ref[0] = jnp.sum(
        (db[:, None, :] <= thr[:, :, None]).astype(jnp.int32), axis=-1)
    topk_ref[0] = _block_smallest(db, k)                  # (B, k)


@functools.partial(jax.jit, static_argnames=(
    "k", "block_n", "block_b", "rows", "scalar", "n_total", "interpret"))
def probe_blocks(
    store: jax.Array,          # (N_pad, d_pad) — padded by ops.py
    preds: jax.Array,          # (1, d_pad) scalar | (d_pad, B_pad) panel
    thresholds: jax.Array,     # (B_pad, T) per-predicate threshold vectors
    valid: jax.Array | None = None,  # (1, 1) int32 n_valid | (1, N_pad) mask
    *,
    k: int,
    block_n: int,
    block_b: int | None = None,
    rows: str = "static",      # static | prefix | mask
    scalar: bool = False,
    n_total: int = 0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-block partials: (counts (nblocks, B_pad, T) int32, smallest
    (nblocks, B_pad, k) f32). ``k`` is at most ``MAX_SELECT_K`` or equal to
    ``block_n`` (the whole tile, unselected)."""
    if not (k <= MAX_SELECT_K or k == block_n):
        raise ValueError(f"in-kernel top-k takes k <= {MAX_SELECT_K} or "
                         f"k == block_n ({block_n}), got k={k}")
    n_pad, d = store.shape
    b_pad, t = thresholds.shape
    bb = b_pad if block_b is None else block_b
    nblocks, nbt = n_pad // block_n, b_pad // bb
    kernel = functools.partial(_probe_kernel, k=k, block_n=block_n,
                               rows=rows, scalar=scalar, n_total=n_total)
    in_specs = [pl.BlockSpec((block_n, d), lambda i, j: (i, 0))]
    operands = [store]
    if rows == "prefix":
        in_specs.insert(0, pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                                        memory_space=pltpu.SMEM))
        operands.insert(0, valid)
    elif rows == "mask":
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, i)))
        operands.append(valid)
    if scalar:
        in_specs.append(pl.BlockSpec((1, d), lambda i, j: (0, 0)))
    else:
        in_specs.append(pl.BlockSpec((d, bb), lambda i, j: (0, j)))
    in_specs.append(pl.BlockSpec((bb, t), lambda i, j: (j, 0)))
    operands += [preds, thresholds]
    return pl.pallas_call(
        kernel,
        grid=(nblocks, nbt),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bb, t), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bb, k), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, b_pad, t), jnp.int32),
            jax.ShapeDtypeStruct((nblocks, b_pad, k), f32),
        ],
        interpret=interpret_mode(interpret),
    )(*operands)
