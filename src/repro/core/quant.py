"""Quantized matrix compute — the MMU's number formats (paper §5.4).

The MMU consumes int8 or int16 fixed-point operands and always emits int16
activations for the NVU ("the output of the MMU is written out ... as 16-bit
fixed point values").  We implement symmetric linear quantization with
per-column (per-output-feature) weight scales and per-row (per-token)
activation scales, plus the quantized-dense building block used by the
model zoo and the npec executor when `npe_quant` is on.

Per-row activation scales make a token's MMU result depend on that
token's own activations alone: whole prefill, chunked prefill and
(batched) decode give it the same numerics, and a causal prefill stays
causal.  A per-tensor scale would tie every row to the largest
activation of the whole tile, rows after it included, and coarsen the
int8 step as prompts grow.

lax.dot_general with int8 operands and preferred_element_type=int32 lowers
onto the MXU's native int8 path on TPU (2x the bf16 rate — the analogue of
the paper's dual-int8-per-DSP trick); the Pallas kernel
repro.kernels.quant_matmul is the hand-tiled version with fused dequant +
PWL epilogue.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    """Symmetric-quantized tensor: values in int8/int16, float scale."""
    q: jnp.ndarray        # int8 or int16
    scale: jnp.ndarray    # f32; per-tensor () or per-channel (..., 1)

    @property
    def bits(self) -> int:
        return 8 if self.q.dtype == jnp.int8 else 16

    def dequantize(self) -> jnp.ndarray:
        return self.q.astype(jnp.float32) * self.scale


def _qdtype(bits: int):
    return {8: jnp.int8, 16: jnp.int16}[bits]


def quantize(x: jnp.ndarray, bits: int = 8,
             axis: Optional[int] = None) -> QTensor:
    """Symmetric quantization; `axis` = channel axis for per-channel scales
    (None = per-tensor)."""
    xf = x.astype(jnp.float32)
    if axis is None:
        amax = jnp.max(jnp.abs(xf))
    else:
        red = tuple(i for i in range(x.ndim) if i != (axis % x.ndim))
        amax = jnp.max(jnp.abs(xf), axis=red, keepdims=True)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(xf / scale), -qmax - 1, qmax).astype(_qdtype(bits))
    return QTensor(q, scale)


def fake_quantize(x: jnp.ndarray, bits: int = 8,
                  axis: Optional[int] = None) -> jnp.ndarray:
    """Quantize-dequantize (straight-through in the backward pass)."""
    qt = quantize(x, bits, axis)
    y = qt.dequantize().astype(x.dtype)
    return x + jax.lax.stop_gradient(y - x)


def int_matmul(aq: jnp.ndarray, bq: jnp.ndarray) -> jnp.ndarray:
    """Integer matmul with int32 accumulation (..., M, K) @ (K, N)."""
    return jax.lax.dot_general(
        aq, bq, (((aq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def quant_dense(x: jnp.ndarray, w: QTensor, bias: Optional[jnp.ndarray] = None,
                act_bits: int = 8) -> jnp.ndarray:
    """The MMU primitive on (rows, K) activations: quantize each row with
    its own scale, integer matmul, dequantize.

    Row scales are (rows, 1) and weight scales per output column (1, N),
    so dequantization is one outer-product multiply in the epilogue —
    exactly the MMU's "accumulate then quantize" stage.
    """
    dt = x.dtype
    xa = quantize(x, act_bits, axis=0)
    acc = int_matmul(xa.q, w.q)                        # int32
    out = acc.astype(jnp.float32) * (xa.scale * w.scale.reshape(1, -1))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    # MMU output is written to MMEM as 16-bit fixed point for the NVU.
    return out.astype(dt)


def dense_maybe_quant(x: jnp.ndarray, w: jnp.ndarray,
                      bias: Optional[jnp.ndarray] = None,
                      npe_quant: bool = False, bits: int = 8) -> jnp.ndarray:
    """Dense layer that routes through the MMU when the NPE mode is on.

    `w` is kept in float master form (training still works); quantization is
    applied functionally, matching the paper's post-training quantization
    flow ([28] Q8BERT-style symmetric).  Lead axes are flattened to rows
    and each row is quantized on its own, so the result of a stacked
    (R, K) input equals that of R one-row calls, bitwise.
    """
    if not npe_quant:
        return x @ w if bias is None else x @ w + bias
    *lead, k = x.shape
    x2 = x.reshape(-1, k)
    if bits == 8:
        # True integer path: int8 x int8 -> int32 is exact for K <= 2^17.
        wq = quantize(w, bits, axis=1)
        y = quant_dense(x2, wq, bias, act_bits=bits)
    else:
        # 16-bit MMU mode.  int16 products overflow int32 accumulators and
        # the TPU MXU has no int16 mode, so the 16-bit variant is modeled as
        # fake-quantization to the int16 grid with f32 accumulation — the
        # quantization error (the quantity under study) is identical; only
        # accumulator rounding differs (f32 vs the FPGA's wide adders).
        xq = fake_quantize(x2.astype(jnp.float32), bits, axis=0)
        wq = fake_quantize(w.astype(jnp.float32), bits, axis=1)
        y = xq @ wq
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        y = y.astype(x.dtype)
    return y.reshape(*lead, w.shape[1])
