"""Trip-count-expanded HLO cost parser: verified against analytically known
programs (the measurement instrument for §Roofline must itself be tested)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import expanded_cost, parse_module, peak_rates


def _cost_of(fn, *specs):
    comp = jax.jit(fn).lower(*specs).compile()
    return expanded_cost(comp.as_text(), 1)


def test_plain_matmul_flops():
    f = lambda a, b: a @ b
    c = _cost_of(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert abs(c.flops - 2 * 64 ** 3) / (2 * 64 ** 3) < 0.05


def test_scanned_matmul_trip_expansion():
    def f(ws, x):
        def body(h, w):
            return h @ w, None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    c = _cost_of(f, jax.ShapeDtypeStruct((10, 64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((64, 64), jnp.float32))
    expect = 10 * 2 * 64 ** 3
    assert c.unknown_trip_loops == 0
    assert abs(c.flops - expect) / expect < 0.05


def test_nested_scan_trip_expansion():
    def f(ws, x):
        def outer(h, w):
            def inner(h2, _):
                return h2 @ w, None
            h2, _ = jax.lax.scan(inner, h, None, length=3)
            return h2, None
        h, _ = jax.lax.scan(outer, x, ws)
        return h

    c = _cost_of(f, jax.ShapeDtypeStruct((5, 32, 32), jnp.float32),
                 jax.ShapeDtypeStruct((32, 32), jnp.float32))
    expect = 5 * 3 * 2 * 32 ** 3
    assert c.unknown_trip_loops == 0
    assert abs(c.flops - expect) / expect < 0.05


def test_collective_formulas():
    from repro.launch.hlo_cost import _collective_traffic
    assert _collective_traffic("all-reduce", 100, 4) == pytest.approx(150.0)
    assert _collective_traffic("all-gather", 100, 4) == pytest.approx(75.0)
    assert _collective_traffic("reduce-scatter", 100, 4) == 300.0
    assert _collective_traffic("collective-permute", 100, 4) == 100.0


def test_parse_module_structure():
    txt = """
HloModule m

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x: f32[8]) -> f32[] {
  %x = f32[8]{0} parameter(0)
  ROOT %r = f32[] reduce(%x, %c), dimensions={0}, to_apply=%add
}
"""
    comps, entry = parse_module(txt)
    assert entry == "%main"
    assert "%add" in comps


def test_peak_rates_by_device_kind():
    """v5e carries its published peaks; a kind missing from the table is an
    error, never a default."""
    assert peak_rates("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError, match="no roofline peaks"):
        peak_rates("TPU v99")
