"""NVU unified nonlinearity engine tests (paper §4, §6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import nvu


KEY = jax.random.PRNGKey(0)


def test_pwl_exp_accuracy():
    x = jnp.linspace(-18.0, 0.0, 512)
    err = jnp.max(jnp.abs(nvu.nvu_exp(x) - jnp.exp(x)))
    assert err < 5e-3


@pytest.mark.parametrize("fn,ref", [
    (nvu.nvu_gelu, lambda x: jax.nn.gelu(x, approximate=False)),
    (nvu.nvu_silu, jax.nn.silu),
    (nvu.nvu_tanh, jnp.tanh),
    (nvu.nvu_sigmoid, jax.nn.sigmoid),
    (nvu.nvu_softplus, jax.nn.softplus),
    (nvu.nvu_relu2, lambda x: jnp.square(jax.nn.relu(x))),
])
def test_elementwise_wide_range(fn, ref):
    """Linear-tail functions must stay accurate OUTSIDE the table interval."""
    x = jnp.linspace(-30.0, 30.0, 2001)
    err = jnp.max(jnp.abs(fn(x) - ref(x)))
    assert err < 2e-2, float(err)


def test_rsqrt_scale_free():
    """Mantissa normalization: relative error flat across 12 decades."""
    x = jnp.logspace(-6, 6, 500)
    rel = jnp.abs(nvu.nvu_rsqrt(x) - jax.lax.rsqrt(x)) * jnp.sqrt(x)
    assert float(jnp.max(rel)) < 2e-3


def test_reciprocal_scale_free():
    x = jnp.logspace(-6, 6, 500)
    rel = jnp.abs(nvu.nvu_reciprocal(x) - 1.0 / x) * x
    assert float(jnp.max(rel)) < 6e-3


def test_softmax_rows_sum_to_one():
    x = jax.random.normal(KEY, (16, 128)) * 5
    s = nvu.nvu_softmax(x)
    np.testing.assert_allclose(np.asarray(jnp.sum(s, -1)), 1.0, atol=5e-3)


def test_softmax_close_to_exact():
    x = jax.random.normal(KEY, (16, 128)) * 3
    err = jnp.max(jnp.abs(nvu.nvu_softmax(x) - jax.nn.softmax(x, -1)))
    assert float(err) < 3.0e-2   # 16 segments
    err32 = jnp.max(jnp.abs(nvu.nvu_softmax(x, segments=32) - jax.nn.softmax(x, -1)))
    assert float(err32) < 8e-3   # error shrinks with segment count


def test_softmax_masked():
    x = jax.random.normal(KEY, (4, 32))
    mask = jnp.arange(32) < 20
    s = nvu.nvu_softmax(x, where=mask[None, :])
    assert float(jnp.max(jnp.abs(jnp.where(mask, 0.0, s)))) == 0.0
    np.testing.assert_allclose(np.asarray(s.sum(-1)), 1.0, atol=5e-3)


def test_layernorm_close():
    x = jax.random.normal(KEY, (8, 256)) * 4 + 1.5
    g = jax.random.normal(jax.random.PRNGKey(1), (256,))
    b = jax.random.normal(jax.random.PRNGKey(2), (256,))
    mu = x.mean(-1, keepdims=True)
    ref = (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5) * g + b
    assert float(jnp.max(jnp.abs(nvu.nvu_layernorm(x, g, b) - ref))) < 2e-2


def test_rmsnorm_close():
    x = jax.random.normal(KEY, (8, 256)) * 2
    g = jnp.ones((256,)) * 1.5
    ref = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g
    assert float(jnp.max(jnp.abs(nvu.nvu_rmsnorm(x, g) - ref))) < 1e-2


def test_fixed_mode_softmax_still_normalized():
    x = jax.random.normal(KEY, (8, 64)) * 4
    s = nvu.nvu_softmax(x, fixed=True)
    np.testing.assert_allclose(np.asarray(s.sum(-1)), 1.0, atol=1e-2)


def test_fixed_mode_layernorm():
    x = jax.random.normal(KEY, (4, 128))
    g, b = jnp.ones((128,)), jnp.zeros((128,))
    mu = x.mean(-1, keepdims=True)
    ref = (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5)
    got = nvu.nvu_layernorm(x, g, b, fixed=True)
    assert float(jnp.max(jnp.abs(got - ref))) < 3e-2


# --- property-based: the engine approximates ANY registered function -------

@settings(max_examples=25, deadline=None)
@given(st.floats(-15.0, 15.0), st.sampled_from(["gelu", "silu", "tanh", "sigmoid"]))
def test_property_pointwise_error_bounded(x0, name):
    from repro.core import pwl
    fn = {"gelu": lambda v: jax.nn.gelu(v, approximate=False),
          "silu": jax.nn.silu, "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}[name]
    approx = {"gelu": nvu.nvu_gelu, "silu": nvu.nvu_silu,
              "tanh": nvu.nvu_tanh, "sigmoid": nvu.nvu_sigmoid}[name]
    x = jnp.float32(x0)
    assert abs(float(approx(x) - fn(x))) < 2.5e-2


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(2, 64), st.floats(0.1, 8.0))
def test_property_softmax_invariants(rows, cols, scale):
    x = jax.random.normal(KEY, (rows, cols)) * scale
    s = nvu.nvu_softmax(x)
    assert bool(jnp.all(s >= -1e-6))
    np.testing.assert_allclose(np.asarray(s.sum(-1)), 1.0, atol=1e-2)
    # shift invariance (max-subtraction)
    s2 = nvu.nvu_softmax(x + 100.0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), atol=1e-3)
