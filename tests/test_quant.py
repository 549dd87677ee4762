"""MMU quantization tests (paper §5.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quant

KEY = jax.random.PRNGKey(0)


def test_quantize_roundtrip_int8():
    x = jax.random.normal(KEY, (64, 32))
    qt = quant.quantize(x, 8)
    err = jnp.max(jnp.abs(qt.dequantize() - x))
    assert float(err) <= float(qt.scale) * 0.51


def test_per_channel_tighter_than_per_tensor():
    # one channel with tiny magnitude: per-channel scales recover it
    x = jnp.concatenate([jax.random.normal(KEY, (32, 7)),
                         0.01 * jax.random.normal(KEY, (32, 1))], axis=1)
    pt = quant.quantize(x, 8, axis=None).dequantize()
    pc = quant.quantize(x, 8, axis=1).dequantize()
    err_pt = float(jnp.max(jnp.abs((pt - x)[:, 7])))
    err_pc = float(jnp.max(jnp.abs((pc - x)[:, 7])))
    assert err_pc < err_pt


def test_int_matmul_matches_float_path():
    a = jax.random.randint(KEY, (16, 32), -100, 100, jnp.int8)
    b = jax.random.randint(jax.random.PRNGKey(1), (32, 8), -100, 100, jnp.int8)
    got = quant.int_matmul(a, b)
    want = np.asarray(a, np.int64) @ np.asarray(b, np.int64)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)
    assert got.dtype == jnp.int32


@pytest.mark.parametrize("bits,tol", [(8, 0.03), (16, 3e-4)])
def test_quant_dense_relative_error(bits, tol):
    x = jax.random.normal(KEY, (32, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 64)) / np.sqrt(128)
    ref = x @ w
    got = quant.dense_maybe_quant(x, w, npe_quant=True, bits=bits)
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert rel < tol, rel


def test_fake_quantize_straight_through_gradient():
    x = jax.random.normal(KEY, (16,))
    g = jax.grad(lambda v: jnp.sum(quant.fake_quantize(v, 8)))(x)
    np.testing.assert_allclose(np.asarray(g), 1.0)


def test_bias_path():
    x = jax.random.normal(KEY, (4, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    b = jax.random.normal(jax.random.PRNGKey(2), (8,))
    got = quant.dense_maybe_quant(x, w, b, npe_quant=True, bits=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w + b),
                               atol=2e-3)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 64), st.integers(2, 64), st.sampled_from([8, 16]))
def test_property_quant_error_bounded_by_scale(m, k, bits):
    """|dequant(q(x)) - x| <= scale/2 everywhere (symmetric rounding)."""
    x = jax.random.normal(jax.random.PRNGKey(m * 1000 + k), (m, k))
    qt = quant.quantize(x, bits)
    err = jnp.max(jnp.abs(qt.dequantize() - x))
    assert float(err) <= float(qt.scale) * 0.51


def test_fixedpoint_quantize_grid():
    from repro.core import fixedpoint as fp
    x = jnp.array([0.1, -0.3, 1.23456, 100.0, -200.0])
    q = fp.quantize(x, fp.Q16_8)
    # on the 2^-8 grid
    np.testing.assert_allclose(np.asarray(q * 256), np.round(np.asarray(q * 256)), atol=1e-5)
    # saturation
    assert float(fp.quantize(jnp.array([1e6]), fp.Q16_8)[0]) == fp.Q16_8.max_val
    assert float(fp.quantize(jnp.array([-1e6]), fp.Q16_8)[0]) == fp.Q16_8.min_val


def test_fixedpoint_mul_add():
    from repro.core import fixedpoint as fp
    a, b = jnp.float32(1.5), jnp.float32(2.25)
    assert float(fp.fixed_mul(a, b, fp.Q16_8)) == 3.375
    assert float(fp.fixed_add(a, b, fp.Q16_8)) == 3.75
