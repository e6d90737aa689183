"""Pure-jnp oracles for the fused semantic-histogram probe (scalar + batched)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32
# f32 passes on the MXU: the default rounds operands to bf16 on a TPU,
# ~1e-3 of cosine distance, enough to move a row across a threshold
HIGHEST = jax.lax.Precision.HIGHEST


def cosine_probe_ref(store: jax.Array, pred: jax.Array, thresholds: jax.Array,
                     k: int) -> tuple[jax.Array, jax.Array]:
    """store (N, d); pred (d,); thresholds (T,). Returns
    (counts (T,) int32, k smallest cosine distances (k,) f32 ascending)."""
    sims = jnp.einsum("nd,d->n", store.astype(f32), pred.astype(f32),
                      precision=HIGHEST)
    dists = 1.0 - sims
    counts = (dists[None, :] <= thresholds[:, None]).sum(axis=1).astype(jnp.int32)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top


def cosine_probe_batch_ref(store: jax.Array, preds: jax.Array,
                           thresholds: jax.Array, k: int,
                           ) -> tuple[jax.Array, jax.Array]:
    """store (N, d); preds (B, d); thresholds (B, T). Returns
    (counts (B, T) int32, k smallest distances (B, k) f32 ascending)."""
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = 1.0 - sims                                      # (B, N)
    counts = (dists[:, None, :] <= thresholds[:, :, None]).sum(
        axis=-1).astype(jnp.int32)                          # (B, T)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top


def cosine_probe_batch_masked_ref(store: jax.Array, n_valid,
                                  preds: jax.Array, thresholds: jax.Array,
                                  k: int) -> tuple[jax.Array, jax.Array]:
    """Oracle for the masked prefix probe: rows >= n_valid are +inf."""
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = 1.0 - sims                                      # (B, N)
    live = jnp.arange(store.shape[0])[None, :] < n_valid
    dists = jnp.where(live, dists, jnp.inf)
    counts = (dists[:, None, :] <= thresholds[:, :, None]).sum(
        axis=-1).astype(jnp.int32)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top


def cosine_probe_batch_rowmask_ref(store: jax.Array, mask: jax.Array,
                                   preds: jax.Array, thresholds: jax.Array,
                                   k: int) -> tuple[jax.Array, jax.Array]:
    """Oracle for the per-row-mask probe: rows with mask == 0 are +inf
    (tombstones / hot-tail dead slots — live rows are not a prefix)."""
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = 1.0 - sims                                      # (B, N)
    dists = jnp.where(mask[None, :] != 0, dists, jnp.inf)
    counts = (dists[:, None, :] <= thresholds[:, :, None]).sum(
        axis=-1).astype(jnp.int32)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top
