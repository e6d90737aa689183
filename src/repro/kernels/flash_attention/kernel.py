"""Flash attention forward Pallas kernel (TPU, GQA-aware).

Blocking mirrors ``repro.models.flash_ref``: grid (B, H, nq, nk) with the KV
axis innermost (sequential on TPU), online-softmax running (m, l, acc) in VMEM
scratch that persists across the nk iterations; the output tile is normalized
and written once at kj == nk-1. The (Sq, Sk) score matrix never exists.

VMEM per step (qc=kc=512, D=128, f32 acc): q 128KB + k/v 256KB + acc 256KB —
well under v5e's 16MB with double buffering. MXU dims (qc x D) x (D x kc) are
128-aligned.
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


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale: float, causal: bool, window: int | None,
                      qc: int, kc: int, sq: int, sk: int, nk: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(f32) * scale            # (qc, D)
    k = k_ref[0, 0].astype(f32)                    # (kc, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)  # (qc, kc)

    q_pos = qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
    k_pos = kj * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
    ok = (k_pos < sk) & (q_pos < sq)
    if causal:
        ok &= k_pos <= q_pos
        if window is not None:
            ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    m_scr[...] = m_new
    v = v_ref[0, 0].astype(f32)                    # (kc, D)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "qc", "kc", "rep",
                     "sq", "sk", "interpret"),
)
def flash_fwd(
    q: jax.Array,   # (B, H, Sq_pad, D)
    k: jax.Array,   # (B, Hkv, Sk_pad, D)
    v: jax.Array,
    *,
    sq: int,
    sk: int,
    rep: int,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    qc: int = 512,
    kc: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    B, H, sq_pad, D = q.shape
    nk = k.shape[2] // kc
    nq = sq_pad // qc
    scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        qc=qc, kc=kc, sq=sq, sk=sk, nk=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qc, D), lambda b, h, qi, kj: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kc, D), lambda b, h, qi, kj: (b, h // rep, kj, 0)),
            pl.BlockSpec((1, 1, kc, D), lambda b, h, qi, kj: (b, h // rep, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qc, D), lambda b, h, qi, kj: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, sq_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qc,), f32),
            pltpu.VMEM((qc,), f32),
            pltpu.VMEM((qc, D), f32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
