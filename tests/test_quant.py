"""Quantization substrate: formats, STE, saturation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import quant


def test_paper_formats():
    assert quant.ACT_Q6_8.bits == 14 and quant.ACT_Q6_8.frac_bits == 8
    assert quant.WEIGHT_INT8.bits == 8
    assert quant.ACC_INT24.bits == 24
    assert abs(quant.ACT_Q6_8.max_value - (2**13 - 1) / 256.0) < 1e-9


@settings(max_examples=50, deadline=None)
@given(st.floats(-40.0, 40.0))
def test_fake_quant_error_bound(x):
    """Within range: |err| <= LSB/2; outside: saturates."""
    spec = quant.ACT_Q6_8
    y = float(quant.fake_quant(jnp.float32(x), spec))
    if spec.min_value <= x <= spec.max_value:
        assert abs(y - x) <= spec.scale / 2 + 1e-7
    else:
        assert y in (spec.min_value, spec.max_value)


def test_int_roundtrip_exact_on_grid():
    spec = quant.ACT_Q6_8
    codes = jnp.arange(spec.qmin, spec.qmax + 1, 37)
    x = codes * spec.scale
    back = quant.dequantize_int(quant.quantize_int(x, spec), spec)
    np.testing.assert_allclose(back, x, atol=0)


def test_ste_gradient_passthrough():
    g = jax.grad(lambda x: quant.fake_quant(x, quant.ACT_Q6_8))(1.2345)
    assert abs(g - 1.0) < 1e-6
    # saturated region still passes gradient (clip has zero grad only
    # through the clip; STE round passes) — check it's finite
    g2 = jax.grad(lambda x: quant.fake_quant(x, quant.ACT_Q6_8))(100.0)
    assert np.isfinite(g2)


def test_weight_int8_range():
    w = jnp.asarray([-1.0, -0.5, 0.0, 0.5, 0.9921875])
    q = quant.quantize_int(w, quant.WEIGHT_INT8, jnp.int8)
    assert int(q.min()) >= -128 and int(q.max()) <= 127
    back = quant.dequantize_int(q, quant.WEIGHT_INT8)
    np.testing.assert_allclose(back, w, atol=quant.WEIGHT_INT8.scale / 2)


@pytest.mark.parametrize("name", ["sigmoid", "tanh"])
def test_rom_gates_equal_float_gates_on_cpu(name):
    """The QAT gates read the host ROM. On the CPU, where the ROM was
    built, they equal fake_quant(fn(x)) on the whole grid domain, in
    value and in gradient."""
    fn, rom = {"sigmoid": (jax.nn.sigmoid, quant.rom_sigmoid),
               "tanh": (jnp.tanh, quant.rom_tanh)}[name]
    spec = quant.ACT_Q6_8
    x = jnp.arange(2 * spec.qmin, 2 * spec.qmax + 1) * spec.scale

    def ref(v):
        return quant.fake_quant(fn(v), spec)

    got, want = jax.jit(rom)(x), jax.jit(ref)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    grad = jax.vmap(jax.grad(rom))
    np.testing.assert_array_equal(np.asarray(grad(x)),
                                  np.asarray(jax.vmap(jax.grad(ref))(x)))
