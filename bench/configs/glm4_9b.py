"""Plain float32 reference of the glm4_9b stage as it is served.

GLM-4-9B (THUDM/glm-4-9b) as the program configures it: token embedding,
then pre-norm blocks x += Wo Attn(RoPE(Wq h + bq), RoPE(Wk h + bk),
Wv h + bv) with h = RMSNorm(x), and x += Wd (SiLU(Wg h2) * Wu h2) with
h2 = RMSNorm(x); grouped-query attention, 32 query heads over 2 kv heads
of 128 dims, causal; a final RMSNorm and the untied output head.  RMSNorm
takes the file's eps (1e-6) and RoPE rotates the file's share of each
head (all 128 dims) in rotate-half layout at base 10000, as the program
does (GLM-4 itself takes eps 1.5625e-7 and rotates half of each head;
the file lists both departures under `reduced`).

`layout` gives the served parameter tree's shapes and the normals the
benchmark draws them from; `forward` reads logits at chosen rows.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import refops as ro
from flops import Dims


def dims(c: Dict[str, Any]) -> Dims:
    return Dims(layers=c["num_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["multi_query_group_num"],
                head_dim=c["kv_channels"], d_ff=c["ffn_hidden_size"],
                vocab=c["padded_vocab_size"], gated=True)


def layout(c: Dict[str, Any]) -> Dict[str, Any]:
    m = dims(c)
    L, D, F = m.layers, m.d_model, m.d_ff
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {
        "embed": ro.table(m.vocab, D),
        "lm_head": ro.matrix(D, m.vocab),
        "ln_f": {"gamma": ro.gain(D)},
        "blocks": {
            "ln1": {"gamma": ro.gain(L, D)},
            "ln2": {"gamma": ro.gain(L, D)},
            "wq": ro.matrix(L, D, q), "bq": ro.bias(L, q),
            "wk": ro.matrix(L, D, kv), "bk": ro.bias(L, kv),
            "wv": ro.matrix(L, D, kv), "bv": ro.bias(L, kv),
            "wo": ro.matrix(L, q, D),
            "mlp": {"wg": ro.matrix(L, D, F), "wu": ro.matrix(L, D, F),
                    "wd": ro.matrix(L, F, D)},
        },
    }


@jax.jit
def _embed(p, tokens):
    return p["embed"][tokens].astype(ro.F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta",
                                   "rotary", "bits"))
def _layer(x, lp, heads: int, kv_heads: int, eps: float, theta: float,
           rotary: float, bits: Optional[int]):
    h = ro.rmsnorm(x, lp["ln1"]["gamma"], eps)

    def proj(n, nh):
        y = ro.mm(h, lp[f"w{n}"], bits) + lp[f"b{n}"].astype(ro.F32)
        return ro.split_heads(y, nh)
    q = ro.rope(proj("q", heads), theta, rotary)
    k = ro.rope(proj("k", kv_heads), theta, rotary)
    v = proj("v", kv_heads)
    x = x + ro.mm(ro.causal_attention(q, k, v), lp["wo"], bits)
    h2 = ro.rmsnorm(x, lp["ln2"]["gamma"], eps)
    mlp = lp["mlp"]
    g = ro.silu(ro.mm(h2, mlp["wg"], bits)) * ro.mm(h2, mlp["wu"], bits)
    return x + ro.mm(g, mlp["wd"], bits)


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(h, gamma, eps: float):
    return ro.rmsnorm(h, gamma, eps)


def forward(c: Dict[str, Any], params, tokens: np.ndarray, rows: np.ndarray,
            bits: Optional[int] = None):
    """Logits (len(rows), vocab) of the causal model over `tokens`, read
    at `rows`; `bits` rounds every weight product's operands."""
    m = dims(c)
    eps, theta = float(c["layernorm_epsilon"]), float(c["rope_theta"])
    x = _embed(params, jnp.asarray(tokens, jnp.int32))
    for layer in range(m.layers):
        lp = jax.tree.map(lambda a, i=layer: a[i], params["blocks"])
        x = _layer(x, lp, heads=m.heads, kv_heads=m.kv_heads, eps=eps,
                   theta=theta, rotary=float(c["rotary_fraction"]),
                   bits=bits)
    h = _final_norm(x[jnp.asarray(rows)], params["ln_f"]["gamma"], eps)
    return ro.head_logits(h, params["lm_head"], bits)
