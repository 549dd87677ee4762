"""Compile the Pallas kernels and the int8 MMU matmul for a described
TPU v5e, at bert_base widths, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached, so these catch what interpret mode
cannot: block shapes the Mosaic tiling refuses, kernels that ask for
more VMEM than a core has.  Nothing runs; a pass says only that the
chip's compiler accepts the program.  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and a test worker that did so while importing would make the
workers collect different tests.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.quant import dense_maybe_quant
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables for a described chip are written to the persistent
    # cache but cannot be read back without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes, dtype=jnp.float32):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n,act", [(128, 768, 3072, "gelu"),
                                       (4, 768, 768, None)])
def test_quant_matmul_compiles(one_chip, m, k, n, act):
    c = _compile(lambda x, w: ops.quant_matmul(x, w, activation=act,
                                               interpret=False),
                 one_chip, (m, k), (k, n))
    assert _has_kernel(c)


def test_pwl_activation_compiles(one_chip):
    c = _compile(lambda x: ops.pwl_activation(x, "gelu", interpret=False),
                 one_chip, (128, 3072))
    assert _has_kernel(c)


def test_softmax_compiles(one_chip):
    c = _compile(lambda x: ops.softmax(x, interpret=False),
                 one_chip, (12, 128, 128))
    assert _has_kernel(c)


def test_layernorm_compiles(one_chip):
    c = _compile(lambda x, g, b: ops.layernorm(x, g, b, interpret=False),
                 one_chip, (128, 768), (768,), (768,))
    assert _has_kernel(c)


def test_flash_attention_compiles(one_chip):
    c = _compile(lambda q, k, v: ops.flash_attention(q, k, v,
                                                     interpret=False),
                 one_chip, (1, 12, 128, 64), (1, 12, 128, 64),
                 (1, 12, 128, 64))
    assert _has_kernel(c)


def test_int8_dense_compiles_to_int8_dot(one_chip):
    """The NPE-mode MMU matmul of a batched decode step: int8 operands,
    int32 accumulation."""
    c = _compile(lambda x, w: dense_maybe_quant(x, w, npe_quant=True,
                                                bits=8),
                 one_chip, (4, 768), (768, 3072))
    hlo = c.as_text()
    assert "s8[" in hlo and "s32[" in hlo
