"""Substrate tests: data pipeline, optimizer, checkpointing, fault
recovery, gradient compression."""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.ckpt import Checkpointer
from repro.config import FaultConfig, OptimizerConfig
from repro.data.pipeline import SyntheticLM
from repro.optim import adamw
from repro.runtime import compression
from repro.runtime.fault import Supervisor, TrainingFailure, run_with_recovery


# --- data -------------------------------------------------------------------

def test_data_deterministic_and_host_sharded():
    full = SyntheticLM(512, 32, 8, seed=3)
    b0 = full.batch_at(5)
    again = SyntheticLM(512, 32, 8, seed=3).batch_at(5)
    np.testing.assert_array_equal(b0["tokens"], again["tokens"])
    # labels are next tokens
    h0 = SyntheticLM(512, 32, 8, seed=3, num_hosts=2, host_id=0).batch_at(5)
    h1 = SyntheticLM(512, 32, 8, seed=3, num_hosts=2, host_id=1).batch_at(5)
    assert h0["tokens"].shape == (4, 32)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_data_learnable_structure():
    """Next token is (mostly) an affine function of the previous one."""
    d = SyntheticLM(128, 64, 4, seed=0, noise=0.0)
    b = d.batch_at(0)
    t, l = b["tokens"][0].astype(np.int64), b["labels"][0].astype(np.int64)
    # find a,c from two transitions, verify on the rest
    # l[i] = (a * t[i] + c) % V
    V = 128
    found = False
    for a in range(1, 2 * V, 2):
        c = (l[0] - a * t[0]) % V
        if np.all((a * t + c) % V == l):
            found = True
            break
    assert found


# --- optimizer ---------------------------------------------------------------

def test_adamw_converges_quadratic():
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, decay_steps=200,
                          schedule="constant", weight_decay=0.0,
                          grad_clip=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init(cfg, params)
    loss = lambda p: jnp.sum(jnp.square(p["w"] - 1.0))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw.update(cfg, g, state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), 1.0, atol=1e-2)


def test_adamw_grad_clip_bounds_update():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=0, schedule="constant",
                          grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = adamw.init(cfg, params)
    g = {"w": jnp.full(4, 1e6)}
    _, _, metrics = adamw.update(cfg, g, state, params)
    assert float(metrics["grad_norm"]) > 1e5   # raw norm reported


def test_schedule_shapes():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, decay_steps=100,
                          schedule="cosine")
    assert float(adamw.schedule(cfg, jnp.int32(0))) == 0.0
    assert abs(float(adamw.schedule(cfg, jnp.int32(10))) - 1.0) < 1e-6
    assert float(adamw.schedule(cfg, jnp.int32(100))) < 1e-6


# --- checkpointing ------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones(4, jnp.bfloat16)}}
    ck.save(7, tree)
    restored, step = ck.restore(tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    tree = {"x": jnp.zeros(2)}
    for s in (1, 2, 3):
        ck.save(s, tree)
    assert ck.latest_step() == 3
    dirs = sorted(p.name for p in tmp_path.glob("step_*"))
    assert dirs == ["step_00000002", "step_00000003"]


def test_checkpoint_async(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=True)
    ck.save(1, {"x": jnp.arange(10)})
    ck.wait()
    _, step = ck.restore({"x": jnp.zeros(10, jnp.int32)})
    assert step == 1


# --- fault tolerance -----------------------------------------------------------

def test_recovery_from_injected_nan():
    sup = Supervisor(FaultConfig(inject_nan_at_step=3, max_restarts=2))
    state = {"restored": 0, "completed_steps": []}

    def loop(start):
        for s in range(start, 6):
            sup.check_loss(s, 1.0)   # injection turns step 3 into NaN once
            state["completed_steps"].append(s)
        return {"ok": True}

    def restore():
        state["restored"] += 1
        return 2                      # pretend checkpoint was at step 2

    out = run_with_recovery(loop, restore, sup)
    assert out["ok"] and state["restored"] == 1
    assert sup.events[0].kind == "nan"
    assert 3 in state["completed_steps"][-4:]   # step 3 retried fine


def test_recovery_gives_up_after_max_restarts():
    sup = Supervisor(FaultConfig(max_restarts=1))

    def loop(start):
        raise TrainingFailure("always")

    with pytest.raises(TrainingFailure, match="max_restarts"):
        run_with_recovery(loop, lambda: 0, sup)


def test_straggler_detection():
    sup = Supervisor(FaultConfig(step_deadline_sec=0.1))
    sup.check_deadline(5, elapsed=0.5)
    assert sup.events and sup.events[0].kind == "straggler"


def test_end_to_end_training_recovers_from_crash(tmp_path):
    from repro.launch.train import Trainer, make_run
    run = make_run("granite_moe_1b_a400m", smoke=True, steps=12, batch=2,
                   seq=32, ckpt_dir=str(tmp_path),
                   fault=FaultConfig(inject_crash_at_step=6, max_restarts=2))
    import dataclasses
    run = dataclasses.replace(
        run, checkpoint=dataclasses.replace(run.checkpoint, interval=4))
    out = Trainer(run, log=lambda *a: None).train()
    assert out["restarts"] == 1
    assert out["fault_events"][0].kind == "crash"
    assert math.isfinite(out["final_loss"])


# --- gradient compression -------------------------------------------------------

def test_compression_error_feedback_reduces_bias():
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=512)
                          .astype(np.float32))}
    err = compression.init_error(g)
    acc_plain = jnp.zeros(512)
    acc_comp = jnp.zeros(512)
    for _ in range(50):
        deq, err = compression.compress_decompress(g, err)
        acc_comp = acc_comp + deq["w"]
        acc_plain = acc_plain + g["w"]
    # error feedback keeps the accumulated compressed sum close
    rel = float(jnp.linalg.norm(acc_comp - acc_plain)
                / jnp.linalg.norm(acc_plain))
    assert rel < 1e-2, rel


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 300))
def test_compression_single_step_error_bounded(n):
    g = {"w": jnp.asarray(np.random.default_rng(n).normal(size=n)
                          .astype(np.float32))}
    deq, err = compression.compress_decompress(g, compression.init_error(g))
    amax = float(jnp.max(jnp.abs(g["w"])))
    assert float(jnp.max(jnp.abs(err["w"]))) <= amax / 127.0 * 0.51 + 1e-7
