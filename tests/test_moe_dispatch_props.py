"""MoE dispatch-semantics property tests (compiled stream vs models/moe).

Random (S, E, k, capacity_factor, router_act) draws assert the compiled
MoE block (`npec.trace_moe_block` executed functionally) reproduces
`models/moe.apply` EXACTLY on the discrete routing decisions:
  * top-k gather indices == `jax.lax.top_k` of the router probabilities;
  * gate values, including the softmax-gate renormalization over the
    selected k (and its absence for sigmoid routers);
  * capacity-overflow drops — the dispatch buffer holds at most C slot
    rows per expert, token-slots past capacity scatter to nothing, and
    the combine output matches `moe.apply` (dropped slots contribute
    zero, gates NOT renormalized after the drop).

Hypothesis drives the random draws; the deterministic sweep below
exercises the same properties on fixed corner draws.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro import npec
from repro.config import MoEConfig
from repro.configs import get_config
from repro.models import common as cm
from repro.models import moe as moe_mod


def _moe_cfg(E, k, cf, router_act, *, npe_pwl=False):
    base = get_config("granite_moe_1b_a400m", smoke=True)
    cfg = dataclasses.replace(
        base, dtype="float32", num_layers=1, d_model=16, d_ff=8,
        moe=MoEConfig(num_experts=E, top_k=k, capacity_factor=cf,
                      router_act=router_act))
    return cfg.with_npe(quant_bits=8, segments=16) if npe_pwl else cfg


def _run_block(cfg, S, seed=0):
    """Execute the compiled MoE block (with routing debug outputs) and the
    moe.apply reference on the same random batch; returns
    (out, gates, ids, buf, ref_out, layer_params, x)."""
    key = jax.random.PRNGKey(seed)
    kp, kx = jax.random.split(key)
    params = cm.init_params(moe_mod.specs(cfg, 1), kp)
    x = jax.random.normal(kx, (2, S, cfg.d_model), jnp.float32)
    g = npec.trace_moe_block(cfg, S, debug_outputs=True)
    with jax.disable_jit():
        res = npec.execute(g, {"blocks": {"moe": params}}, {"x": x},
                           cfg=cfg)
        layer_p = jax.tree.map(lambda a: a[0], params)
        ref = moe_mod.apply(cfg, layer_p, x)
    out, gates, ids, buf = (np.asarray(r, np.float32) if i != 2
                            else np.asarray(r)
                            for i, r in enumerate(res.outputs))
    return out, gates, ids, buf, np.asarray(ref, np.float32), layer_p, x


def _reference_routing(cfg, layer_p, x):
    """The routing decisions recomputed from models/moe internals (the
    same functions `moe.apply` calls): probabilities, top-k gates + ids,
    and the renormalized gates."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        layer_p["router"].astype(jnp.float32))
    probs = moe_mod._router_probs(cfg, logits)
    gate_vals, expert_ids = jax.lax.top_k(probs, cfg.moe.top_k)
    if cfg.moe.router_act == "softmax" and cfg.moe.top_k > 1:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    return np.asarray(gate_vals, np.float32), np.asarray(expert_ids)


def _assert_dispatch_semantics(cfg, S, seed=0):
    out, gates, ids, buf, ref, layer_p, x = _run_block(cfg, S, seed)
    m = cfg.moe
    cap = npec.moe_capacity(cfg, S)

    # 1. top-k gather indices + gate renormalization: exact
    want_gates, want_ids = _reference_routing(cfg, layer_p, x)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(gates, want_gates)

    # 2. capacity-overflow drops: replay the GShard cumsum in numpy and
    # check every token-slot's fate in the dispatch buffer — kept slots
    # hold the token row bitwise, dropped slots scatter to nothing
    B = x.shape[0]
    xk = np.repeat(np.asarray(x, np.float32), m.top_k, axis=1)
    ids_flat = ids.reshape(B, S * m.top_k)
    expect_buf = np.zeros((B, m.num_experts, cap, cfg.d_model), np.float32)
    n_dropped = 0
    for b in range(B):
        fill = np.zeros(m.num_experts, np.int64)
        for t, e in enumerate(ids_flat[b]):
            if fill[e] < cap:
                expect_buf[b, e, fill[e]] = xk[b, t]
            else:
                n_dropped += 1
            fill[e] += 1
    np.testing.assert_array_equal(buf, expect_buf)

    # 3. combine output == moe.apply (dropped slots contribute zero)
    np.testing.assert_allclose(out, ref, rtol=0, atol=1e-6)
    return n_dropped


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(1, 4), st.integers(4, 12),
       st.floats(0.25, 2.0), st.booleans(), st.integers(0, 3))
def test_dispatch_matches_moe_apply_random(E, k_raw, S, cf, sigmoid, seed):
    k = 1 + (k_raw - 1) % E
    cfg = _moe_cfg(E, k, cf, "sigmoid" if sigmoid else "softmax")
    _assert_dispatch_semantics(cfg, S, seed)


# --- deterministic sweep: fixed corner draws --------------------------------

SWEEP = [
    # (E, k, S, capacity_factor, router_act)
    (4, 2, 8, 1.25, "softmax"),      # granite-like: renormalized top-2
    (4, 1, 8, 1.25, "sigmoid"),      # llama4-like: sigmoid top-1
    (2, 1, 8, 0.25, "softmax"),      # tight capacity -> forced drops
    (8, 4, 6, 2.0, "softmax"),       # k*S/E > 1 with slack capacity
    (3, 3, 5, 1.0, "sigmoid"),       # k == E, ragged sizes
]


@pytest.mark.parametrize("E,k,S,cf,act", SWEEP)
def test_dispatch_matches_moe_apply_sweep(E, k, S, cf, act):
    cfg = _moe_cfg(E, k, cf, act)
    _assert_dispatch_semantics(cfg, S, seed=1)


def test_tight_capacity_actually_drops():
    """The forced-drop corner must really exercise overflow: capacity 1
    per expert with 8 token-slots routed to 2 experts drops >= 6 slots,
    and the compiled combine still matches moe.apply exactly."""
    cfg = _moe_cfg(2, 1, 0.25, "softmax")
    assert npec.moe_capacity(cfg, 8) == 1
    n_dropped = _assert_dispatch_semantics(cfg, 8, seed=2)
    assert n_dropped >= 6 * 2                    # per batch row, B=2


def test_dispatch_semantics_npe_pwl_mode():
    """Same properties with the PWL router (NPE mode): the discrete
    routing decisions come from PWL softmax probabilities on BOTH sides,
    so indices/gates/drops still match exactly."""
    cfg = _moe_cfg(4, 2, 1.25, "softmax", npe_pwl=True)
    _assert_dispatch_semantics(cfg, 8, seed=3)
    cfg = _moe_cfg(4, 1, 1.25, "sigmoid", npe_pwl=True)
    _assert_dispatch_semantics(cfg, 8, seed=4)
