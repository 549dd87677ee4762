"""Seeded weights, made on the device in one jitted call.

The configuration's reference module gives the layout (`layout(config)`:
a tree of `refops.Leaf`), and this draws every leaf from its normal in
float32 and stores it in the type the weights are served in.  The same
seed gives the same weights.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from refops import Leaf


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def make_params(layout: Any, seed: int, dtype: str) -> Any:
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    dt = jnp.dtype(dtype)

    def draw(key):
        out = []
        for i, leaf in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            out.append((leaf.mean + leaf.std * z).astype(dt))
        return out

    arrays = jax.jit(draw)(jax.random.PRNGKey(seed))
    return jax.tree.unflatten(treedef, arrays)
