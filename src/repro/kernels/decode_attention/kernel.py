"""Flash-decode Pallas kernel: one new token vs a long (compressed) KV cache.

The online hot loop of BOTH serving paths in this framework: ordinary decode
(decode_32k / long_500k cells) and the paper's compressed-KV-cache batching
(§3.2) where 128 image caches answer one yes/no prompt in a single batched
forward.

Grid (B, Hkv, nk): the cache streams HBM->VMEM in (kc, D) tiles (fp8/bf16
stay compressed in HBM — upcast happens in VMEM); running (m, l, acc) for the
``rep`` query heads of this KV head live in VMEM scratch across nk steps.
kv_valid masking supports ring buffers and per-image compressed lengths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

f32 = jnp.float32
NEG_INF = -1e30


def _decode_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, kc: int, nk: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(f32) * scale            # (rep, D)
    k = k_ref[0, 0].astype(f32)                    # (kc, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)  # (rep, kc)
    pos = kj * kc + jax.lax.broadcasted_iota(jnp.int32, (1, kc), 1)
    s = jnp.where(pos < valid_ref[0], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    m_scr[...] = m_new
    v = v_ref[0, 0].astype(f32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "kc", "interpret"))
def decode_fwd(
    q: jax.Array,        # (B, Hkv, rep, D)
    k: jax.Array,        # (B, Hkv, L_pad, D)
    v: jax.Array,
    kv_valid: jax.Array,  # (B,) int32 — per-sequence valid cache length
    *,
    scale: float,
    kc: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    B, Hkv, rep, D = q.shape
    nk = k.shape[2] // kc
    kernel = functools.partial(_decode_kernel, scale=scale, kc=kc, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, kj: (b,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, rep, D), lambda b, h, kj: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, kc, D), lambda b, h, kj: (b, h, kj, 0)),
            pl.BlockSpec((1, 1, kc, D), lambda b, h, kj: (b, h, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, D), lambda b, h, kj: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep,), f32),
            pltpu.VMEM((rep,), f32),
            pltpu.VMEM((rep, D), f32),
        ],
        interpret=interpret_mode(interpret),
    )(kv_valid, q, k, v)
