"""Jitted wrapper: score kernel + top-keep selection + gather."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.expected_attention.kernel import ea_scores

f32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("keep", "kc", "interpret"))
def compress(
    k: jax.Array,      # (B, S, Hkv, D)
    v: jax.Array,
    q_mu: jax.Array,   # (Hkv, rep, D)
    q_var: jax.Array,
    *,
    keep: int,
    kc: int = 1024,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, S, Hkv, D = k.shape
    kcc = min(kc, max(128, S))
    pad = (-S) % kcc
    kt = jnp.pad(jnp.moveaxis(k, 1, 2), ((0, 0), (0, 0), (0, pad), (0, 0)))
    vt = jnp.pad(jnp.moveaxis(v, 1, 2), ((0, 0), (0, 0), (0, pad), (0, 0)))
    scores = ea_scores(kt, vt, q_mu, q_var, kc=kcc, interpret=interpret)
    scores = scores[:, :, :S]                                  # (B,Hkv,S)
    _, idx = jax.lax.top_k(scores, min(keep, S))               # (B,Hkv,keep)
    idx = jnp.sort(idx, axis=-1)
    bidx = jnp.arange(B)[:, None, None]
    hidx = jnp.arange(Hkv)[None, :, None]
    k_c = k[bidx, idx, hidx].transpose(0, 2, 1, 3)             # (B,keep,Hkv,D)
    v_c = v[bidx, idx, hidx].transpose(0, 2, 1, 3)
    return k_c, v_c, idx.transpose(0, 2, 1)
