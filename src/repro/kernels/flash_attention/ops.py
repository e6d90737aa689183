"""Jitted wrapper: (B, S, H, D) layout in, pad to tiles, kernel, unpad."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_fwd


def _pad_axis(x, m, axis):
    pad = (-x.shape[axis]) % m
    if not pad:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "q_chunk", "kv_chunk", "q_offset",
                                             "interpret"))
def flash_attention(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    scale: float | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    assert q_offset == 0, "prefill/train always start at position 0"
    B, sq, H, D = q.shape
    sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    qc = min(q_chunk, max(128, sq))
    kc = min(kv_chunk, max(128, sk))
    qt = _pad_axis(jnp.moveaxis(q, 1, 2), qc, 2)    # (B, H, Sq_pad, D)
    kt = _pad_axis(jnp.moveaxis(k, 1, 2), kc, 2)
    vt = _pad_axis(jnp.moveaxis(v, 1, 2), kc, 2)
    out = flash_fwd(qt, kt, vt, sq=sq, sk=sk, rep=rep, causal=causal,
                    window=window, scale=scale, qc=qc, kc=kc,
                    interpret=interpret)
    return jnp.moveaxis(out, 2, 1)[:, :sq]
