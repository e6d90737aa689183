"""K-means assignment Pallas kernel (paper §3.2 sample selection).

Fused distance + argmin: streams (block_n, d) tiles of the embedding store,
keeps the full centroid matrix resident in VMEM, one MXU matmul per tile,
emits only int32 assignments. Centroid updates (segment sums) happen in
ops.py.

The score tile is computed centroid-major, (C, block_n), so the argmin
reduces over sublanes and each tile's assignments land as one lane-dense
(1, block_n) row of a (1, N) output — a 1-D (block_n,) block fails the
chip compiler's layout check once C is large. The argmin is a min followed
by a first-index min over the matches, which keeps ``jnp.argmin``'s
lowest-index tie rule.

VMEM: the resident (C, d) centroid panel is what grows — at C=1024 and
d=1152 its double buffer alone is 9.4 MB — so ``assign_blocks`` raises
the scoped VMEM limit to what the blocks need (v5e has 128 MiB of VMEM;
the compiler's default scope is 16 MiB) rather than shrinking the store
block. The matmul runs at default precision: an assignment only has to be
a good partition, and the index computes its bounds from the assignment it
gets (radii are exact over each cluster's actual members).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

f32 = jnp.float32

_VMEM_CAP = 100 * 1024 ** 2


def _assign_kernel(x_ref, c_ref, c2_ref, out_ref):
    x = x_ref[...].astype(f32)                 # (block_n, d)
    c = c_ref[...].astype(f32)                 # (C, d)
    # ||x-c||^2 ranking = -2 c.x + ||c||^2 (||x||^2 constant per row)
    score = -2.0 * jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())),
        preferred_element_type=f32) + c2_ref[...]        # (C, block_n)
    best = jnp.min(score, axis=0, keepdims=True)         # (1, block_n)
    ids = jax.lax.broadcasted_iota(jnp.int32, score.shape, 0)
    out_ref[...] = jnp.min(jnp.where(score == best, ids, score.shape[0]),
                           axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def assign_blocks(x: jax.Array, centroids: jax.Array, *, block_n: int = 512,
                  interpret: bool | None = None) -> jax.Array:
    """(N, d) rows -> (N,) int32 nearest-centroid ids. N need not be a
    multiple of ``block_n``: the last block is partial (its out-of-range
    rows are never written back), so the store is never padded or copied."""
    n, d = x.shape
    C = centroids.shape[0]
    c2 = jnp.sum(centroids.astype(f32) ** 2, axis=1)[:, None]   # (C, 1)
    vmem = 4 * (2 * block_n * d + 2 * C * d + 4 * C * block_n) + (4 << 20)
    out = pl.pallas_call(
        _assign_kernel,
        grid=(pl.cdiv(n, block_n),),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((C, d), lambda i: (0, 0)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(max(vmem, 16 << 20), _VMEM_CAP)),
        interpret=interpret_mode(interpret),
    )(x, centroids, c2)
    return out[0]
