"""Plain jax.numpy building blocks of the references in `configs/*.py`.

Everything is float32 at `Precision.HIGHEST`, so that a TPU computes the
matrix products in full float32 and not in one bfloat16 pass.  `mm(...,
bits=b)` is the same product with both operands rounded to a symmetric
b-bit grid (weights per output column, activations per row): the
lower-precision control that the correctness limits are set against.

Nothing here imports the program under test.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclass(frozen=True)
class Leaf:
    """One weight: its shape and the normal it is drawn from."""
    shape: Tuple[int, ...]
    std: float
    mean: float = 0.0


def matrix(*shape: int) -> Leaf:
    """A weight matrix, fan-in scaled on its second-to-last axis."""
    return Leaf(tuple(shape), shape[-2] ** -0.5)


def gain(*shape: int) -> Leaf:
    return Leaf(tuple(shape), 0.05, 1.0)


def bias(*shape: int) -> Leaf:
    return Leaf(tuple(shape), 0.02)


def table(*shape: int) -> Leaf:
    return Leaf(tuple(shape), 0.02)


def fake_quant(x, bits: int, axis: int):
    """Round `x` to a symmetric `bits`-bit grid, one scale per slice along
    every axis but `axis` (the reduced one)."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale


def mm(a, w, bits: Optional[int] = None):
    """(..., K) @ (K, N) in float32; `bits` rounds both operands first."""
    a = a.astype(F32)
    w = w.astype(F32)
    if bits is not None:
        a = fake_quant(a, bits, axis=-1)          # one scale per row
        w = fake_quant(w, bits, axis=0)           # one scale per column
    return jnp.dot(a, w, precision=HIGHEST)


_mm = jax.jit(mm, static_argnames=("bits",))


def layernorm(x, gamma, beta, eps: float):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(F32) \
        + beta.astype(F32)


def rmsnorm(x, gamma, eps: float):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * gamma.astype(F32)


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta: float, fraction: float = 1.0):
    """Rotate (H, T, D) by position t along T, rotate-half layout over the
    first `fraction` of the D lanes (the rest pass unrotated): of those R
    lanes, the first R/2 pair with the last R/2."""
    d = int(x.shape[-1] * fraction)
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[-2], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., : d // 2], x[..., d // 2: d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def causal_attention(q, k, v):
    """q (A, T, D); k, v (KV, T, D) with A a multiple of KV (query head i
    reads kv head i // (A // KV)).  Returns (T, A * D)."""
    a, t, d = q.shape
    group = a // k.shape[0]
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("atd,asd->ats", q, k, precision=HIGHEST) * d ** -0.5
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    z = jnp.einsum("ats,asd->atd", p, v, precision=HIGHEST)
    return jnp.transpose(z, (1, 0, 2)).reshape(t, a * d)


def split_heads(x, heads: int):
    """(T, H * D) -> (H, T, D)."""
    t = x.shape[0]
    return jnp.transpose(x.reshape(t, heads, -1), (1, 0, 2))


def head_logits(h, w, bits: Optional[int], block: int = 32768):
    """h (R, D) @ w (D, V) in column blocks, so that a large head is never
    held in float32 whole."""
    parts = [_mm(h, w[:, i:i + block], bits=bits)
             for i in range(0, w.shape[1], block)]
    return jnp.concatenate(parts, axis=-1)
