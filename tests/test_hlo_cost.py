"""The loop-aware HLO cost model vs hand-computed costs (roofline substrate)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_cost import analyze_hlo, parse_computations, shape_bytes


def test_shape_bytes():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
    assert shape_bytes("pred[7]") == 7


def test_scan_flops_counted_with_trip_count():
    def g(a, b):
        def body(c, _):
            return jnp.tanh(c @ b), None
        c, _ = jax.lax.scan(body, a, None, length=10)
        return c

    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    comp = jax.jit(g).lower(a, b).compile()
    c = analyze_hlo(comp.as_text())
    expect = 10 * 2 * 512 ** 3
    assert c.flops == pytest.approx(expect, rel=0.01)
    assert any(t == 10.0 for _, t in c.while_trips)


def test_nested_scan_flops():
    def g(a, b):
        def outer(c, _):
            def inner(d, _):
                return d @ b, None
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None
        c, _ = jax.lax.scan(outer, a, None, length=4)
        return c

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    comp = jax.jit(g).lower(a, b).compile()
    c = analyze_hlo(comp.as_text())
    assert c.flops == pytest.approx(12 * 2 * 256 ** 3, rel=0.01)


def test_hbm_bytes_dominated_by_streamed_operand():
    # one big matmul: traffic >= operand+output sizes, not absurdly more
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
    b = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
    comp = jax.jit(f).lower(a, b).compile()
    c = analyze_hlo(comp.as_text())
    lo = 3 * 2048 * 2048 * 4
    assert lo <= c.hbm_bytes <= 4 * lo


def test_roofline_terms_and_bottleneck():
    from repro.analysis.roofline import analyze

    def f(a, b):
        return jnp.tanh(a @ b)

    a = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16)
    comp = jax.jit(f).lower(a, b).compile()
    r = analyze(comp.as_text(), model_flops=2 * 4096 ** 3)
    assert r.flops == pytest.approx(2 * 4096 ** 3, rel=0.01)
    assert r.useful_ratio == pytest.approx(1.0, rel=0.01)
    assert r.bottleneck in ("compute", "memory")
    assert r.compute_term > 0 and r.memory_term > 0


def test_peaks_table_raises_for_unknown_kind_and_keeps_link_rate():
    from repro.analysis.roofline import V5E, peaks

    # 1,600 Gbit/s per chip over 4 ICI links: 50 GB/s on the ring link
    # the collective wire formulas count
    assert peaks(V5E).link_bw == pytest.approx(50e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
