"""Serving-engine validation (repro.npec.runtime + batched decode streams).

Four gates:
  * functional — a batched decode stream (B in {2, 4, 8} slots sharing
    ONE stream, merged B-row projections, per-slot cache banks) executes
    bitwise-equal to B independent per-sequence `DecodeSession` rollouts
    (float 1e-6 / NPE 5e-3, the shared tests/conftest.py tolerances), and
    the full engine (compiled prefill -> batched decode) reproduces a
    token-by-token per-sequence rollout's generations exactly;
  * structure — PE-row occupancy from `mmu_tiling_summary` scales
    ~linearly with B (>= 4x the 1-row baseline at B=8, ISSUE gate) and
    weight projections are B-row tiles;
  * scheduling/fairness — FIFO admission over ragged prompt lengths,
    slot reuse, per-slot capacity guards (pos overflow raises instead of
    silently masking to garbage);
  * cycle regression — recomputing the serve table reproduces
    results/npec_serve_cycles.json exactly (cost-only engine rows: the
    record is pure cycle model, regenerate via `python -m benchmarks.run`
    if the compiler changed).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import cycles as cy
from repro.core.overlay import NPEHardware
from repro import npec
from repro.npec.runtime import NPEEngine

HW = NPEHardware(vrwidth=1024)


def _smoke_cfg(name="glm4_9b"):
    from repro.configs import get_config
    return dataclasses.replace(get_config(name, smoke=True),
                               dtype="float32")


def _params(cfg):
    import jax
    from repro.models import registry
    return registry.init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Functional: batched stream vs B independent per-sequence rollouts
# ---------------------------------------------------------------------------

def _batched_vs_sequential_err(name: str, B: int, *, steps: int,
                               npe: bool, bits: int) -> float:
    """Max abs step-output error, batched B-slot stream vs B independent
    per-sequence DecodeSession rollouts over the same token streams."""
    import jax
    import jax.numpy as jnp

    cfg = _smoke_cfg(name)
    params = _params(cfg)
    T = steps + 2
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (B, steps),
                                         0, cfg.vocab_size))
    npe_cfg = cfg.with_npe(quant_bits=bits, segments=16) if npe else None
    bat = npec.DecodeSession(
        npec.compile_decode(cfg, T, HW, bits=bits, batch=B), params,
        cfg=npe_cfg)
    seqs = [npec.DecodeSession(
        npec.compile_decode(cfg, T, HW, bits=bits), params, cfg=npe_cfg)
        for _ in range(B)]
    err = 0.0
    with jax.disable_jit():
        for t in range(steps):
            got = np.asarray(bat.step(toks[:, t]))
            for s in range(B):
                ref = np.asarray(seqs[s].step(
                    jnp.asarray(toks[s:s + 1, t:t + 1])))
                err = max(err, float(np.max(np.abs(got[s] - ref[0, 0]))))
    assert list(bat.pos) == [steps] * B
    return err


@pytest.mark.parametrize("B", [2, 4, 8])
def test_batched_stream_matches_sequential_float(B, float_tol):
    """ISSUE gate: B in {2, 4, 8} slots, bitwise vs sequential rollouts."""
    assert _batched_vs_sequential_err("glm4_9b", B, steps=4, npe=False,
                                      bits=16) < float_tol


def test_batched_stream_matches_sequential_npe_mode(npe_tol):
    """Same in NPE mode (int8 MMU + PWL NVU both sides): `core.quant`
    scales each activation row on its own, so each merged-tile row is
    quantized exactly as its 1-row per-sequence counterpart and batched
    streams stay faithful; gated at the shared NPE tolerance."""
    assert _batched_vs_sequential_err("bert_base", 4, steps=4, npe=True,
                                      bits=8) < npe_tol


def test_engine_matches_per_sequence_rollout(float_tol):
    """Compiled prefill + batched decode reproduces a pure per-sequence
    rollout: same generated tokens for a single request."""
    import jax
    import jax.numpy as jnp

    cfg = _smoke_cfg("bert_base")
    params = _params(cfg)
    T, gen = 16, 4
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (5,), 0,
                                           cfg.vocab_size))
    eng = NPEEngine(cfg, HW, slots=2, capacity=T, max_new_tokens=gen,
                    params=params)
    eng.submit(prompt)
    stats = eng.run()
    sess = npec.DecodeSession(npec.compile_decode(cfg, T, HW, bits=16),
                              params)
    with jax.disable_jit():
        for t in range(len(prompt)):
            out = sess.step(jnp.asarray(prompt[t:t + 1][None]))
        want = [int(np.argmax(np.asarray(out)[0, -1]))]
        for _ in range(gen - 1):
            out = sess.step(jnp.asarray([[want[-1]]], dtype=jnp.int32))
            want.append(int(np.argmax(np.asarray(out)[0, -1])))
    assert stats.requests[0].generated == want


# ---------------------------------------------------------------------------
# Structure: occupancy scaling with batch
# ---------------------------------------------------------------------------

def test_occupancy_scales_with_batch():
    """ISSUE gate: PE-row occupancy grows ~linearly in B — >= 4x the
    1-row baseline at B=8 — and the merged projections are B-row tiles."""
    sh = cy.BertShape(seq=64)
    eff = {}
    for B in (1, 2, 4, 8):
        compiled = npec.compile_decode_bert_shape(HW, sh, 128, 16,
                                                  layers=1, batch=B)
        eff[B] = compiled.mmu_tiling_summary()["efficiency"]
        rows = {ins.shape[0] for ins in compiled.instrs
                if ins.unit == "MMU"}
        assert B in rows, f"no merged {B}-row projection tiles at B={B}"
    assert eff[1] < eff[2] < eff[4] < eff[8]
    assert eff[8] >= 4 * eff[1]


def test_batched_decode_step_cycles_cost_model():
    """The cost-model wrapper under ragged-tile charging: the padded tile
    cycles ARE the charged schedule, so batching's win shows directly —
    cycles/token falls as B-row tiles fill PE rows and tok/s grows
    ~linearly in B — while the ideal MAC-rate floor stays flat per
    token and tile streaming never loses to the whole-op DAG."""
    sh = cy.BertShape(seq=64)
    r1 = cy.batched_decode_step_cycles(HW, sh, 128, 1, 8)
    r8 = cy.batched_decode_step_cycles(HW, sh, 128, 8, 8)
    assert r8["cycles_per_token"] < r1["cycles_per_token"]
    assert r8["ideal_step_cycles"] / 8 == pytest.approx(
        r1["ideal_step_cycles"], rel=0.05)
    assert r8["tok_s"] > 4 * r1["tok_s"]
    assert r8["mmu_efficiency"] > 4 * r1["mmu_efficiency"]
    for r in (r1, r8):
        assert r["dag_cycles"] >= r["streaming_cycles"]
        assert r["total_cycles"] == r["streaming_cycles"]


# ---------------------------------------------------------------------------
# Slot lifecycle: capacity guards, fairness, admission order
# ---------------------------------------------------------------------------

def test_batched_capacity_guard_names_slot():
    """Per-slot pos overflow raises (ISSUE satellite: no silent masking
    to garbage); inactive slots hold their counters and never trip it."""
    cfg = _smoke_cfg("bert_base")
    params = _params(cfg)
    sess = npec.DecodeSession(
        npec.compile_decode(cfg, 3, HW, bits=16, batch=2), params)
    toks = np.zeros(2, np.int32)
    sess.step(toks)
    sess.step(toks, active=np.array([True, False]))
    sess.step(toks, active=np.array([True, False]))
    assert list(sess.pos) == [3, 1]
    # slot 0 is full; stepping only slot 1 is still fine
    sess.step(toks, active=np.array([False, True]))
    with pytest.raises(ValueError, match=r"slot\(s\) \[0\]"):
        sess.step(toks)
    sess.reset_slot(0)
    assert list(sess.pos) == [0, 2]
    sess.step(toks, active=np.array([True, False]))   # recycled slot works


def test_engine_submit_capacity_guard():
    cfg = _smoke_cfg("bert_base")
    eng = NPEEngine(cfg, HW, slots=2, capacity=8, max_new_tokens=4)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit(np.arange(6, dtype=np.int32))      # 6 + 4 > 8


def test_engine_fairness_ragged_prompts():
    """FIFO admission over ragged prompts on a 2-slot pool: every request
    completes with exactly its token budget, admission follows submit
    order, and slots are reused (cost-only engine: pure cycle model)."""
    cfg = _smoke_cfg("bert_base")
    eng = NPEEngine(cfg, HW, slots=2, capacity=24, max_new_tokens=3)
    lens = [4, 12, 6, 9, 5, 11]
    for n in lens:
        eng.submit(np.arange(n, dtype=np.int32) % cfg.vocab_size)
    stats = eng.run()
    assert len(stats.requests) == len(lens)
    assert all(r.done for r in stats.requests)
    assert all(len(r.generated) == 3 for r in stats.requests)
    admits = [r.admit_cycle for r in stats.requests]
    assert admits == sorted(admits), "admission is not FIFO"
    assert stats.prefills == len(lens)
    assert stats.decode_steps > 0
    rep = stats.report()
    assert rep["p99_ms"] >= rep["p50_ms"] > 0
    assert rep["tokens_per_sec"] > 0


def test_engine_eos_eviction_makes_completions_ragged():
    """ISSUE satellite: the EOS-aware workload samples a stop token per
    request (`SyntheticRequests.eos_id`) and the cost-only engine's
    deterministic synthetic token stream draws from the same alphabet, so
    some requests stop well before their budget — ragged completions, not
    budget-only eviction — and every early stop actually ends on its own
    EOS token."""
    from repro.data.pipeline import SyntheticRequests
    cfg = _smoke_cfg("bert_base")
    eng = NPEEngine(cfg, HW, slots=2, capacity=48, max_new_tokens=24)
    reqs = SyntheticRequests(cfg.vocab_size, max_prompt=8)
    for i in range(8):
        eng.submit(reqs.request(i), eos_id=reqs.eos_id(i))
    stats = eng.run()
    assert all(r.done for r in stats.requests)
    lens = [len(r.generated) for r in stats.requests]
    assert any(n < 24 for n in lens), lens      # EOS fired somewhere
    assert len(set(lens)) > 1, lens             # completions are ragged
    for r in stats.requests:
        if len(r.generated) < r.max_new_tokens:
            assert r.generated[-1] == r.eos_id


def test_engine_drains_queue_with_single_token_requests():
    """Requests that finish at their first (prefill) token — token budget
    1, or EOS on the first token — must not strand the rest of the
    queue: admissions alone count as engine progress."""
    cfg = _smoke_cfg("bert_base")
    eng = NPEEngine(cfg, HW, slots=2, capacity=16, max_new_tokens=1)
    for n in (4, 5, 6, 7, 8):
        eng.submit(np.arange(n, dtype=np.int32) % cfg.vocab_size)
    stats = eng.run()
    assert all(r.done for r in stats.requests)
    assert all(len(r.generated) == 1 for r in stats.requests)
    assert stats.prefills == 5
    assert stats.decode_steps == 0


def test_engine_moe_family_raises_compile_error():
    """ISSUE satellite: MoE decode streams are a ROADMAP follow-up — the
    engine must fail at construction with a CompileError naming the gap,
    not crash mid-schedule."""
    from repro.configs import get_config
    with pytest.raises(npec.CompileError, match="MoE decode streams"):
        NPEEngine(get_config("granite_moe_1b_a400m", smoke=True), HW,
                  slots=2, capacity=8)


def test_prefill_unsupported_family_raises_compile_error():
    from repro.configs import get_config
    with pytest.raises(npec.CompileError):
        npec.trace_prefill(get_config("whisper_base", smoke=True), 8)


# ---------------------------------------------------------------------------
# Chunked prefill: the p99 latency cliff
# ---------------------------------------------------------------------------

def test_chunked_prefill_tames_long_prompt_latency_cliff():
    """ISSUE gate: a long prompt admitted mid-decode stalls in-flight
    decodes for its whole prefill; chunking at 64 interleaves decode
    steps between slices and cuts the victim's worst inter-token gap to
    < 25% of the unchunked engine (cost-only: pure cycle model).

    Sized at S=512 because MMU ragged-tile padding (any <=128-row matmul
    charges a full 128-row PE tile) caps the per-slice saving for short
    prompts — a 64-row slice of a 256-row prompt still pays half the
    projection tiles, so only long prompts show the full cliff."""
    from repro.npec.runtime import inter_token_gaps

    cfg = dataclasses.replace(_smoke_cfg("bert_base"), max_position=768)
    S = 512

    def worst_gap(chunk):
        eng = NPEEngine(cfg, HW, slots=2, capacity=S + 20,
                        max_new_tokens=12, prefill_chunk=chunk)
        eng.submit(np.arange(6, dtype=np.int32) % cfg.vocab_size)
        for _ in range(3):            # victim is mid-decode...
            eng.step()
        eng.submit(np.arange(S, dtype=np.int32) % cfg.vocab_size)
        stats = eng.run()
        victim = stats.requests[0]
        assert len(victim.generated) == 12
        return max(inter_token_gaps([victim]))

    unchunked, chunked = worst_gap(None), worst_gap(64)
    assert chunked < 0.25 * unchunked, (chunked, unchunked)


# ---------------------------------------------------------------------------
# Cycle-count regression guard vs results/npec_serve_cycles.json
# ---------------------------------------------------------------------------

def test_serve_cycle_record_regression():
    """The committed serve record must be reproducible bit-for-bit from
    the current compiler + engine cycle accounting."""
    from conftest import assert_cycle_record
    assert_cycle_record("npec_serve_cycles.json", "npec_serve_cycles/v1",
                        "npec_serve")
