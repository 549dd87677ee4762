"""Pallas kernel validation: every kernel vs its pure-jnp oracle
(interpret=True on CPU), swept over shapes and dtypes, plus hypothesis
property tests.  Tolerances are tight (1e-5-ish) because kernel and oracle
compute the same PWL math — approximation error cancels out.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import pwl
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


# --- pwl_eval ---------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (128,), (33, 130), (4, 256, 19), (1000,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fn", ["gelu", "exp", "silu"])
def test_pwl_eval_kernel_vs_ref(shape, dtype, fn):
    x = (jax.random.normal(KEY, shape) * 4).astype(dtype)
    got = ops.pwl_activation(x, fn)
    want = ref.pwl_eval(x.astype(jnp.float32), pwl.get_table(fn, 16))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 300), st.integers(4, 40))
def test_pwl_eval_property_shapes(n, seg):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,)) * 6
    got = ops.pwl_activation(x, "gelu", segments=seg)
    want = ref.pwl_eval(x, pwl.get_table("gelu", seg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --- quant_matmul -----------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 128, 64), (256, 256, 256),
                                   (100, 300, 70), (512, 768, 256)])
def test_quant_matmul_vs_ref(m, k, n):
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) / np.sqrt(k)
    got = ops.quant_matmul(x, w, block_m=min(256, max(8, m)), block_n=128,
                           block_k=128)
    # oracle: same quantization, jnp integer matmul
    from repro.core.quant import quantize
    xq, wq = quantize(x, 8), quantize(w, 8, axis=1)
    want = ref.quant_matmul(xq.q, wq.q, xq.scale, wq.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_quant_matmul_fused_gelu():
    x = jax.random.normal(KEY, (64, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128)) / 16.0
    got = ops.quant_matmul(x, w, activation="gelu", block_m=64,
                           block_n=128, block_k=128)
    from repro.core.quant import quantize
    xq, wq = quantize(x, 8), quantize(w, 8, axis=1)
    want = ref.quant_matmul(xq.q, wq.q, xq.scale, wq.scale,
                            table=pwl.get_table("gelu", 16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_quant_matmul_accuracy_vs_float():
    """End accuracy: int8 kernel output within ~2% of float matmul."""
    x = jax.random.normal(KEY, (128, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 128)) / np.sqrt(512)
    got = ops.quant_matmul(x, w, block_m=128, block_n=128, block_k=128)
    want = x @ w
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.03, rel


# --- nvu_softmax ------------------------------------------------------------

@pytest.mark.parametrize("rows,cols", [(8, 128), (100, 512), (256, 1000)])
def test_softmax_kernel_vs_ref(rows, cols):
    x = jax.random.normal(KEY, (rows, cols)) * 3
    got = ops.softmax(x)
    want = ref.nvu_softmax(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_softmax_kernel_vs_exact():
    x = jax.random.normal(KEY, (64, 256)) * 2
    got = ops.softmax(x, segments=32)
    want = jax.nn.softmax(x, -1)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-2
    np.testing.assert_allclose(np.asarray(got.sum(-1)), 1.0, atol=5e-3)


def test_softmax_kernel_causal():
    x = jax.random.normal(KEY, (128, 128)) * 2
    got = ops.softmax(x, causal=True, block_rows=64)
    want = ref.nvu_softmax(x, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --- nvu_layernorm ----------------------------------------------------------

@pytest.mark.parametrize("rows,cols,rms", [(16, 768, False), (100, 512, False),
                                           (64, 1024, True), (3, 256, True)])
def test_layernorm_kernel_vs_ref(rows, cols, rms):
    x = jax.random.normal(KEY, (rows, cols)) * 3 + 0.7
    g = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (cols,))
    b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (cols,))
    if rms:
        got = ops.rmsnorm(x, g)
        want = ref.nvu_layernorm(x, g, None, eps=1e-6, rms_only=True)
    else:
        got = ops.layernorm(x, g, b)
        want = ref.nvu_layernorm(x, g, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_layernorm_kernel_vs_exact():
    x = jax.random.normal(KEY, (32, 512)) * 5
    g = jnp.ones((512,))
    got = ops.layernorm(x, g, None, segments=32)
    mu = x.mean(-1, keepdims=True)
    want = (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-2


# --- flash_attention --------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d", [(1, 2, 2, 128, 64),
                                          (2, 4, 2, 256, 64),
                                          (1, 8, 1, 128, 128)])
def test_flash_attention_vs_ref(b, hq, hkv, s, d):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    got = ops.flash_attention(q, k, v, causal=True, use_pwl=False,
                              block_q=64, block_kv=64)
    want = ref.attention(q, k, v, causal=True, use_pwl=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_pwl_close_to_exact():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    got = ops.flash_attention(q, k, v, causal=True, use_pwl=True, segments=32,
                              block_q=64, block_kv=64)
    want = ref.attention(q, k, v, causal=True, use_pwl=False)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2


def test_flash_attention_sliding_window():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    got = ops.flash_attention(q, k, v, causal=True, window=64, use_pwl=False,
                              block_q=64, block_kv=64)
    want = ref.attention(q, k, v, causal=True, window=64, use_pwl=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_mode():
    """Decode: 1 query (padded to a block) over a long cache, causal=False."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 4, 8, 64))
    k = jax.random.normal(ks[1], (2, 2, 512, 64))
    v = jax.random.normal(ks[2], (2, 2, 512, 64))
    got = ops.flash_attention(q, k, v, causal=False, use_pwl=False,
                              block_q=8, block_kv=128)
    want = ref.attention(q, k, v, causal=False, use_pwl=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --- bit-twiddling helpers --------------------------------------------------

def test_recip_rsqrt_bit_tricks():
    """The integer frexp/ldexp in the kernels must match jnp.frexp."""
    from repro.kernels.nvu_softmax import recip_via_pwl
    from repro.kernels.nvu_layernorm import rsqrt_via_pwl
    from repro.kernels.pwl_eval import pack_table

    class FakeRef:
        def __init__(self, arr):
            # packed tables are numpy (concrete); convert so traced
            # fori_loop indices can slice them
            self.arr = jnp.asarray(arr)

        def __getitem__(self, idx):
            return self.arr[idx]

    x = jnp.logspace(-20, 20, 200, dtype=jnp.float32)
    rt = FakeRef(ops.packed_table("recip", 32))
    got = recip_via_pwl(x, rt, 34)
    rel = jnp.abs(got - 1.0 / x) * x
    assert float(jnp.max(rel)) < 2e-3
    st_ = FakeRef(ops.packed_table("rsqrt", 32))
    got2 = rsqrt_via_pwl(x, st_, 34)
    rel2 = jnp.abs(got2 - jax.lax.rsqrt(x)) * jnp.sqrt(x)
    assert float(jnp.max(rel2)) < 2e-3
