"""Public jit'd wrappers around the Pallas kernels.

These handle quantization, padding to tile multiples, table packing, and
dispatch.  `interpret` defaults to the backend: on a TPU the kernels lower
through Mosaic; anywhere else their bodies run in the Pallas interpreter
(the CPU validation mode).  Pass it explicitly to override, as the
topology compile tests do (tests/test_tpu_compile.py).

Models call these only when RunConfig selects the Pallas fast path; the
jnp-level implementations in repro.core.nvu are the default (XLA-fused)
path and the oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import pwl
from repro.core.quant import quantize
from repro.kernels import flash_attention as _fa
from repro.kernels import nvu_layernorm as _ln
from repro.kernels import nvu_softmax as _sm
from repro.kernels import pwl_eval as _pe
from repro.kernels import quant_matmul as _qm


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a kernel call runs in the Pallas interpreter: `interpret`
    when given, else everywhere but on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@functools.lru_cache(maxsize=None)
def packed_table(name: str, segments: int = 16) -> jnp.ndarray:
    return _pe.pack_table(pwl.get_table(name, segments))


def _pad2(x, bm, bn, value=0.0):
    m, n = x.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), constant_values=value)
    return x, m, n


def pwl_activation(x: jnp.ndarray, name: str, segments: int = 16,
                   block_m: int = 256, block_n: int = 512,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Elementwise nonlinearity via the PWL kernel (any input shape)."""
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = min(block_n, -(-n // 128) * 128)          # lane-dim multiple of 128
    rows = -(-n // cols)
    bm = min(block_m, rows)
    rows_p = -(-rows // bm) * bm
    x2 = jnp.pad(flat, (0, rows_p * cols - n)).reshape(rows_p, cols)
    out = _pe.pwl_eval_2d(x2, packed_table(name, segments), block_m=bm,
                          block_n=cols, interpret=interpret_mode(interpret))
    return out.reshape(-1)[:n].reshape(shape).astype(x.dtype)


def quant_matmul(x: jnp.ndarray, w: jnp.ndarray,
                 activation: Optional[str] = None, segments: int = 16,
                 block_m: int = 256, block_n: int = 256, block_k: int = 256,
                 out_dtype=jnp.float32,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """MMU matmul: int8-quantize x (per-tensor) and w (per-channel), run the
    fused kernel, return float activations (optionally PWL-activated)."""
    *lead, kdim = x.shape
    x2 = x.reshape(-1, kdim)
    xq = quantize(x2, 8, axis=None)
    wq = quantize(w, 8, axis=1)

    bm = min(block_m, max(8, x2.shape[0]))
    qx, m0, k0 = _pad2(xq.q, bm, block_k)
    qw, _, n0 = _pad2(wq.q, block_k, block_n)
    ws = jnp.pad(wq.scale.reshape(1, -1), ((0, 0), (0, (-n0) % block_n)))
    tab = packed_table(activation, segments) if activation else None
    out = _qm.quant_matmul(qx, qw, xq.scale.reshape(1), ws, tab,
                           out_dtype=out_dtype, block_m=bm,
                           block_n=block_n, block_k=block_k,
                           interpret=interpret_mode(interpret))
    return out[:m0, :n0].reshape(*lead, n0)


def softmax(x: jnp.ndarray, segments: int = 16, causal: bool = False,
            block_rows: int = 256,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Row softmax over the last axis via the NVU softmax kernel."""
    *lead, n = x.shape
    x2 = x.reshape(-1, n)
    br = min(block_rows, max(8, x2.shape[0]))
    xp, m0, _ = _pad2(x2, br, n, value=0.0)
    out = _sm.nvu_softmax_rows(xp, packed_table("exp", segments),
                               packed_table("recip", segments),
                               block_rows=br, causal=causal,
                               interpret=interpret_mode(interpret))
    return out[:m0].reshape(*lead, n)


def layernorm(x: jnp.ndarray, gamma: jnp.ndarray,
              beta: Optional[jnp.ndarray] = None, eps: float = 1e-5,
              segments: int = 16, rms_only: bool = False,
              block_rows: int = 0,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """LayerNorm/RMSNorm over the last axis via the NVU layernorm kernel."""
    *lead, n = x.shape
    x2 = x.reshape(-1, n)
    if block_rows <= 0:
        # keep in+out+scratch under ~8 MB of VMEM
        block_rows = max(8, min(256, (8 << 20) // (8 * n)))
    xp, m0, _ = _pad2(x2, block_rows, n)
    out = _ln.nvu_layernorm_rows(xp, gamma, beta,
                                 packed_table("rsqrt", segments), eps=eps,
                                 block_rows=block_rows, rms_only=rms_only,
                                 interpret=interpret_mode(interpret))
    return out[:m0].reshape(*lead, n)


def rmsnorm(x, gamma, eps: float = 1e-6, segments: int = 16,
            interpret: Optional[bool] = None):
    return layernorm(x, gamma, None, eps=eps, segments=segments,
                     rms_only=True, interpret=interpret)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    scale: Optional[float] = None, use_pwl: bool = True,
                    segments: int = 16, block_q: int = 256,
                    block_kv: int = 256, interpret: Optional[bool] = None):
    """Flash attention with NVU (PWL) softmax.  Shapes must tile evenly;
    decode (sq != skv) runs with causal=False over the visible cache."""
    sq, skv = q.shape[2], k.shape[2]
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    return _fa.flash_attention(q, k, v, packed_table("exp", segments),
                               packed_table("recip", segments),
                               causal=causal, window=window, scale=scale,
                               use_pwl=use_pwl, block_q=bq, block_kv=bkv,
                               interpret=interpret_mode(interpret))
