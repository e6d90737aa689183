"""Lloyd's k-means built on the assignment kernel; returns medoid sample ids.

The paper selects its KV-batch sample by clustering image embeddings with
K = sample_size and picking the image nearest each centroid (§3.2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kmeans.kernel import assign_blocks
from repro.kernels.kmeans.ref import assign_ref

f32 = jnp.float32


def kmeans(
    x: np.ndarray | jax.Array, k: int, *, iters: int = 10, seed: int = 0,
    block_n: int = 512, impl: str = "pallas", interpret: bool | None = None,
    init_centroids: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Returns (centroids (k, d), assignments (N,)).

    ``x`` may be a host array or the f32 store already on the device; the
    latter is used in place, so clustering a served store holds no second
    device copy of it.

    ``init_centroids`` warm-starts Lloyd's from a previous clustering
    instead of the seeded random draw — the incremental index rebuild
    passes the prior generation's centroids (most rows keep their
    assignment across a small mutation batch, so a couple of refinement
    iterations recover a cold run's quality at a fraction of the cost).
    Must be (k', d) with k' <= N; k is then taken from it.
    """
    rng = np.random.default_rng(seed)
    xd = jnp.asarray(x, f32)
    n, d = xd.shape
    block_n = min(block_n, max(128, 1 << (n - 1).bit_length()))
    if init_centroids is not None:
        init_centroids = np.asarray(init_centroids, np.float32)
        if init_centroids.ndim != 2 or init_centroids.shape[1] != d:
            raise ValueError(
                f"init_centroids {init_centroids.shape} incompatible with "
                f"store dim {d}")
        k = min(len(init_centroids), n)
        cent = jnp.asarray(init_centroids[:k], f32)
    else:
        cent = jnp.asarray(x[rng.choice(n, size=k, replace=False)], f32)

    for _ in range(iters):
        if impl == "pallas":
            assign = assign_blocks(xd, cent, block_n=block_n,
                                   interpret=interpret)
        else:
            assign = assign_ref(xd, cent)
        sums = jax.ops.segment_sum(xd, assign, num_segments=k)
        cnts = jax.ops.segment_sum(jnp.ones((n,), f32), assign, num_segments=k)
        new = sums / jnp.maximum(cnts, 1.0)[:, None]
        # re-seed empty clusters at random points
        empty = cnts < 0.5
        reseed = jnp.asarray(x[rng.choice(n, size=k)], f32)
        cent = jnp.where(empty[:, None], reseed, new)
    if impl == "pallas":
        assign = assign_blocks(xd, cent, block_n=block_n,
                               interpret=interpret)
    else:
        assign = assign_ref(xd, cent)
    return np.asarray(cent), np.asarray(assign)


@jax.jit
def _nearest_rows(x: jax.Array, cent: jax.Array) -> jax.Array:
    """(k,) row of ``x`` nearest each centroid. ``|c|^2`` is constant per
    centroid, so it is left out of the argmin over rows."""
    d2 = (jnp.sum(x * x, axis=1)[:, None]
          - 2.0 * jnp.dot(x, cent.T, precision=jax.lax.Precision.HIGHEST))
    return jnp.argmin(d2, axis=0)


def medoid_sample(x: np.ndarray | jax.Array, k: int, **kw) -> np.ndarray:
    """Indices of the k images nearest the k centroids (diverse sample).

    Clustering and the nearest-image pick both run on the device copy of
    ``x`` (the served store itself when ``x`` is already on the device);
    only the k indices come back to the host."""
    xd = jnp.asarray(x, f32)
    cent, _ = kmeans(xd, k, **kw)
    return np.unique(np.asarray(_nearest_rows(xd, jnp.asarray(cent))))
