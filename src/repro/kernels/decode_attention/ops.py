"""Jitted wrapper for flash-decode."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_fwd


@functools.partial(jax.jit, static_argnames=("scale", "kv_chunk", "window",
                                             "interpret"))
def decode_attention(
    q: jax.Array,     # (B, 1, H, D)
    k: jax.Array,     # (B, L, Hkv, D)
    v: jax.Array,
    *,
    kv_valid=None,    # scalar / (B,) / None
    window=None,      # unused: ring-buffer masking arrives via kv_valid
    scale=None,
    kv_chunk: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    B, _, H, D = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    kc = min(kv_chunk, max(128, L))
    pad = (-L) % kc
    kt = jnp.pad(jnp.moveaxis(k, 1, 2), ((0, 0), (0, 0), (0, pad), (0, 0)))
    vt = jnp.pad(jnp.moveaxis(v, 1, 2), ((0, 0), (0, 0), (0, pad), (0, 0)))
    qt = q[:, 0].reshape(B, Hkv, rep, D)
    if kv_valid is None:
        valid = jnp.full((B,), L, jnp.int32)
    else:
        valid = jnp.broadcast_to(jnp.asarray(kv_valid, jnp.int32), (B,))
    out = decode_fwd(qt, kt, vt, valid, scale=scale, kc=kc,
                     interpret=interpret)
    return out.reshape(B, 1, H, D)
