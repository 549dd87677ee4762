"""A whole run at a tiny size on the CPU (the look for a chip skipped),
with the timed path broken underneath: `correct` has to come out false
for every fault the cell can have.  The faults are planted in the
engine, as a regression would be; the limits are set for the tiny sizes
from the readings in test_bench_control.py.

The cells run on one chip, so there is no exchange between chips to
leave out; `bert_base.encode` ends every request at its prefill, so it
has no decode state to leave unchanged and no batch to halve."""
import time

import numpy as np
import pytest

import bench_tiny
import run

LIMITS = {"bert_base.encode": 0.02, "glm4_9b.chat": 0.3}
SEED = 2147483661


def run_tiny(cell_name, hook=None):
    cell = bench_tiny.tiny_cell(cell_name, limit=LIMITS[cell_name])
    return run.run_cell(cell, SEED, 3.0, False, t_start=time.perf_counter(),
                        engine_hook=hook, require_tpu=False,
                        clock=bench_tiny.Ticks())


def wrap_decode(engine, change):
    """Pass every decode step's (B, V) logits and the session through
    `change`."""
    sess = engine.session
    step = sess.step

    def broken(tokens, active=None):
        before = dict(sess.caches)
        out = np.asarray(step(tokens, active=active))
        return change(out, sess, before)
    sess.step = broken


def altered_token(out, sess, before):
    return np.roll(out, 1, axis=-1)          # argmax moves one id along


def state_unchanged(out, sess, before):
    sess.caches = before                     # the step's appends are lost
    return out


def half_batch(out, sess, before):
    out = out.copy()
    half = out.shape[0] // 2
    out[half:] = out[:out.shape[0] - half]   # second half not computed
    return out


@pytest.mark.parametrize("cell_name", ["bert_base.encode", "glm4_9b.chat"])
def test_sound_run_is_correct(cell_name):
    res = run_tiny(cell_name)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] <= LIMITS[cell_name]
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell_name", ["bert_base.encode", "glm4_9b.chat"])
def test_token_altered_at_prefill_is_caught(cell_name, monkeypatch):
    from repro.npec.runtime import engine as engine_mod

    execute = engine_mod.execute

    def broken(prog, params, feeds, **kw):
        res = execute(prog, params, feeds, **kw)
        res.outputs[0] = np.roll(np.asarray(res.outputs[0]), 1, axis=-1)
        return res
    monkeypatch.setattr(engine_mod, "execute", broken)
    res = run_tiny(cell_name)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_batch])
def test_decode_fault_is_caught(fault):
    res = run_tiny("glm4_9b.chat",
                   hook=lambda eng: wrap_decode(eng, fault))
    assert not res["correct"], (fault.__name__, res["checks"])


def test_a_token_outside_the_vocabulary_reads_null_and_fails():
    import json

    import check

    v = check.judge(np.array([0.0, np.inf]), 0.3, 1)
    assert not v.correct
    assert json.loads(json.dumps(v.checks())) == {
        "max_logit_gap": {"value": None, "limit": 0.3}}
    assert not check.judge(np.zeros(0), 0.3, 0).correct
