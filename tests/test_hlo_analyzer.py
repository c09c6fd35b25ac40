"""The HLO cost analyzer must (a) match XLA's cost_analysis on loop-free
graphs and (b) correctly multiply loop-body costs by static trip counts."""
import jax
import jax.numpy as jnp
import pytest

from repro.parallel.hlo import analyze, parse_hlo


def _compile_text(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    return compiled.as_text(), compiled.cost_analysis()


def test_matmul_flops_match_xla():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    text, cost = _compile_text(lambda x, y: x @ y, a, b)
    got = analyze(text)
    expected = 2 * 64 * 128 * 32
    assert got.flops == pytest.approx(expected, rel=0.01)
    assert cost["flops"] == pytest.approx(expected, rel=0.01)


def test_scan_flops_scale_with_trip_count():
    """XLA cost_analysis counts a scanned matmul ONCE; we must count it x8."""
    w = jax.ShapeDtypeStruct((8, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 32), jnp.float32)

    def fn(w, x):
        def body(h, wi):
            return jnp.tanh(h @ wi), None

        h, _ = jax.lax.scan(body, x, w)
        return h

    text, cost = _compile_text(fn, w, x)
    got = analyze(text)
    one_layer = 2 * 4 * 32 * 32
    assert got.flops == pytest.approx(8 * one_layer, rel=0.05), (
        f"expected {8*one_layer}, analyzer said {got.flops}, xla said {cost['flops']}"
    )
    # demonstrate the xla undercount this module exists to fix
    assert cost["flops"] < got.flops


def test_nested_scan_multiplies():
    w = jax.ShapeDtypeStruct((3, 5, 16, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((2, 16), jnp.float32)

    def fn(w, x):
        def outer(h, wo):
            def inner(h2, wi):
                return h2 @ wi, None

            h2, _ = jax.lax.scan(inner, h, wo)
            return h2, None

        h, _ = jax.lax.scan(outer, x, w)
        return h

    text, _ = _compile_text(fn, w, x)
    got = analyze(text)
    assert got.flops == pytest.approx(15 * 2 * 2 * 16 * 16, rel=0.05)


def test_parse_computations():
    a = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    text, _ = _compile_text(lambda x: jnp.sum(x @ x), a)
    comps, entry = parse_hlo(text)
    assert entry
    assert entry in comps
    assert any(op.op == "dot" for c in comps.values() for op in c.ops)


def test_hbm_bytes_reasonable():
    """Bytes estimate for a simple matmul ≈ operands + result."""
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    text, _ = _compile_text(lambda x, y: x @ y, a, a)
    got = analyze(text)
    expected = 3 * 256 * 256 * 4
    assert expected * 0.8 <= got.hbm_bytes <= expected * 3


def test_collective_ring_factors():
    # 8 host devices were forced in conftest? no — single device here, so
    # build a fake HLO snippet instead.
    text = """
HloModule test

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  ROOT %all-reduce.1 = f32[1024]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    got = analyze(text)
    r = 1024 * 4
    assert got.collective["bytes_by_type"]["all-reduce"] == pytest.approx(2 * 3 / 4 * r)
