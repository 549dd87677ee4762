"""Plain float32 reference of bert_base as it is served.

BERT-base (arXiv:1810.04805) in its causal serving variant: learned
positions and the token-type-0 embedding summed with the token embedding
and layer-normed; then post-norm blocks, X2 = LN(X + Attn(X)) and
X5 = LN(X2 + W2 GELU(W1 X2 + b1) + b2), with each token attending to
itself and the tokens before it; the output head is the token embedding,
transposed (tied).  GELU is the exact erf form; every layer norm takes
eps 1e-12.  This is the model the engine's int8 MMU and PWL NVU
approximate.

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

EPS = 1e-12


def dims(c: Dict[str, Any]) -> Dims:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return Dims(layers=c["num_hidden_layers"], d_model=d, heads=h,
                kv_heads=h, head_dim=d // h, d_ff=c["intermediate_size"],
                vocab=c["vocab_size"], gated=False)


def layout(c: Dict[str, Any]) -> Dict[str, Any]:
    m = dims(c)
    L, D, F = m.layers, m.d_model, m.d_ff
    norm = {"gamma": ro.gain(L, D), "beta": ro.bias(L, D)}
    blocks = {
        "ln1": dict(norm), "ln2": dict(norm),
        "mlp": {"w1": ro.matrix(L, D, F), "b1": ro.bias(L, F),
                "w2": ro.matrix(L, F, D), "b2": ro.bias(L, D)},
    }
    for n in ("q", "k", "v"):
        blocks[f"w{n}"] = ro.matrix(L, D, D)
        blocks[f"b{n}"] = ro.bias(L, D)
    blocks["wo"] = ro.matrix(L, D, D)
    # Position and token-type rows are shared by every prompt.  Drawn at
    # the word table's scale, they dominate the last row of random
    # weights, which then yields nearly one token per seed, so a wrong
    # computation would serve the same tokens as a right one; at a tenth
    # of it the served tokens differ from request to request.
    shared = 0.1 * ro.table(1).std
    return {
        "embed": ro.table(m.vocab, D),
        "pos_embed": ro.Leaf((c["max_position_embeddings"], D), shared),
        "type_embed": ro.Leaf((c["type_vocab_size"], D), shared),
        "ln_embed": {"gamma": ro.gain(D), "beta": ro.bias(D)},
        "blocks": blocks,
    }


@jax.jit
def _embed(p, tokens):
    t = tokens.shape[0]
    x = (p["embed"][tokens].astype(ro.F32)
         + p["pos_embed"][:t].astype(ro.F32)
         + p["type_embed"][0].astype(ro.F32))
    return ro.layernorm(x, p["ln_embed"]["gamma"], p["ln_embed"]["beta"],
                        EPS)


@partial(jax.jit, static_argnames=("heads", "bits"))
def _layer(x, lp, heads: int, bits: Optional[int]):
    def proj(h, n):
        return ro.mm(h, lp[f"w{n}"], bits) + lp[f"b{n}"].astype(ro.F32)
    q, k, v = (ro.split_heads(proj(x, n), heads) for n in "qkv")
    a = ro.mm(ro.causal_attention(q, k, v), lp["wo"], bits)
    x = ro.layernorm(x + a, lp["ln1"]["gamma"], lp["ln1"]["beta"], EPS)
    mlp = lp["mlp"]
    h = ro.gelu(ro.mm(x, mlp["w1"], bits) + mlp["b1"].astype(ro.F32))
    y = ro.mm(h, mlp["w2"], bits) + mlp["b2"].astype(ro.F32)
    return ro.layernorm(x + y, lp["ln2"]["gamma"], lp["ln2"]["beta"], EPS)


def forward(c: Dict[str, Any], params, tokens: np.ndarray, rows: np.ndarray,
            bits: Optional[int] = None):
    """Logits (len(rows), vocab) of the causal model over `tokens`, read
    at `rows`; `bits` rounds every weight product's operands."""
    m = dims(c)
    x = _embed(params, jnp.asarray(tokens, jnp.int32))
    for layer in range(m.layers):
        lp = jax.tree.map(lambda a, i=layer: a[i], params["blocks"])
        x = _layer(x, lp, heads=m.heads, bits=bits)
    h = x[jnp.asarray(rows)]
    return ro.head_logits(h, params["embed"].T, bits)
