"""Expected-Attention scoring Pallas kernel (KV-cache compression, paper §3.2).

score(pos) = ||v_pos|| * sum_r exp( mu_r.k_pos / sqrt(D) + var_r.k_pos^2 / 2D )

One bandwidth-bound pass over the cache: K/V tiles stream HBM->VMEM; the
(kc, D) x (D, rep) moment matmuls hit the MXU; only (kc,) scores return to
HBM (S/D reduction of traffic). Top-keep selection+gather happens in ops.py —
it is O(S log S) on tiny data and not worth a kernel.

Grid (B, Hkv, ns).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode

f32 = jnp.float32


def _ea_kernel(k_ref, v_ref, mu_ref, var_ref, out_ref, *, scale: float):
    k = k_ref[0, 0].astype(f32)                    # (kc, D)
    v = v_ref[0, 0].astype(f32)
    mu = mu_ref[0].astype(f32)                     # (rep, D)
    var = var_ref[0].astype(f32)
    lin = jax.lax.dot_general(k, mu, (((1,), (1,)), ((), ())),
                              preferred_element_type=f32) * scale   # (kc, rep)
    quad = jax.lax.dot_general(k * k, var, (((1,), (1,)), ((), ())),
                               preferred_element_type=f32) * (0.5 * scale * scale)
    e = jnp.exp(jnp.clip(lin + quad, -30.0, 30.0))
    per = e.sum(axis=-1)                           # (kc,)
    vnorm = jnp.sqrt(jnp.sum(v * v, axis=-1))
    out_ref[0, 0] = per * vnorm


@functools.partial(jax.jit, static_argnames=("kc", "interpret"))
def ea_scores(
    k: jax.Array,      # (B, Hkv, S_pad, D)
    v: jax.Array,
    q_mu: jax.Array,   # (Hkv, rep, D)
    q_var: jax.Array,
    *,
    kc: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    B, Hkv, s_pad, D = k.shape
    rep = q_mu.shape[1]
    ns = s_pad // kc
    kernel = functools.partial(_ea_kernel, scale=1.0 / math.sqrt(D))
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, kc, D), lambda b, h, sj: (b, h, sj, 0)),
            pl.BlockSpec((1, 1, kc, D), lambda b, h, sj: (b, h, sj, 0)),
            pl.BlockSpec((1, rep, D), lambda b, h, sj: (h, 0, 0)),
            pl.BlockSpec((1, rep, D), lambda b, h, sj: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, kc), lambda b, h, sj: (b, h, sj)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, s_pad), f32),
        interpret=interpret_mode(interpret),
    )(k, v, q_mu, q_var)
