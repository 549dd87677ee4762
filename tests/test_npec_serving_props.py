"""Property-test suite for the serving stack (chunked prefill +
prefill/decode disaggregation, docs/serving.md + docs/fleet.md).

Hypothesis properties (the deterministic sweeps below cover the same
gates on fixed draws):

  * chunked-prefill equivalence — for random prompt lengths S and chunk
    sizes in {1..S}, running ceil(S/chunk) causal cache slices seeds the
    SAME cache bank as the whole-prompt prefill (float mode: atol 2e-6,
    covering platform-BLAS reduction order; NPE mode: 5e-3) and every
    subsequent decode token is identical;
  * engine conservation — tokens_out == sum(per-request completions), no
    slot ever serves two live requests, and the charged clock is
    monotone across steps, for random workloads x chunk sizes.

Plus the bit-exact guard on results/npec_disagg_cycles.json (the
chunked/disaggregated serving record, benchmarks.paper_tables).
"""
import dataclasses

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import npec
from repro.configs import get_config
from repro.core.overlay import NPEHardware
from repro.npec.runtime import NPEEngine, chunk_spans, inter_token_gaps

HW = NPEHardware(vrwidth=1024)
# chunked-vs-whole cache banks agree op-for-op; the slack covers CPU BLAS
# kernels that order reductions differently for (C, T) vs (S, S) matmul
# shapes (same reason conftest.FLOAT_TOL exists) — decode-token identity
# below is the strict functional gate on top
CHUNK_FLOAT_TOL = 2e-6


def _smoke_cfg(name="bert_base"):
    return dataclasses.replace(get_config(name, smoke=True),
                               dtype="float32")


def _params(cfg):
    import jax
    from repro.models import registry
    return registry.init_params(cfg, jax.random.PRNGKey(0))


def _chunked_banks(cfg, params, prompt, chunk, capacity, npe_cfg=None):
    """Run the prompt as causal cache slices (the engine's chunked-admit
    path, standalone) and return the final {name: (S, hd)} banks."""
    return _chunked_prefill(cfg, params, prompt, chunk, capacity,
                            npe_cfg)[0]


def _chunked_prefill(cfg, params, prompt, chunk, capacity, npe_cfg=None):
    """`_chunked_banks`, and the last prompt row's logits."""
    import jax

    caches = None
    with jax.disable_jit():
        for base, rows in chunk_spans(len(prompt), chunk):
            prog = npec.compile_prefill(cfg, rows, HW, bits=16,
                                        cache_len=capacity)
            if caches is None:
                g = prog.graph
                caches = {name: np.zeros(g.node(nid).shape, np.float32)
                          for name, nid in g.caches.items()}
            feeds = dict(caches)
            feeds["pos_ids"] = np.arange(base, base + rows, dtype=np.int32)
            feeds["tokens"] = np.asarray(prompt[base:base + rows], np.int32)
            res = npec.execute(prog, params, feeds, cfg=npe_cfg)
            caches.update({k: np.asarray(v)
                           for k, v in res.cache_updates.items()})
    S = len(prompt)
    return ({name: arr[:S] for name, arr in caches.items()},
            np.asarray(res[0])[-1])


def _whole_banks(cfg, params, prompt, npe_cfg=None):
    return _whole_prefill(cfg, params, prompt, npe_cfg)[0]


def _whole_prefill(cfg, params, prompt, npe_cfg=None):
    """`_whole_banks`, and the last prompt row's logits."""
    import jax

    prog = npec.compile_prefill(cfg, len(prompt), HW, bits=16)
    with jax.disable_jit():
        res = npec.execute(prog, params,
                           {"tokens": np.asarray(prompt, np.int32)},
                           cfg=npe_cfg)
    return ({k: np.asarray(v) for k, v in res.kv_exports.items()},
            np.asarray(res[0])[-1])


def _assert_banks_match(got, want, tol):
    assert set(got) == set(want)
    for name in sorted(want):
        err = float(np.abs(got[name] - want[name]).max())
        assert err <= tol, f"{name}: max|err|={err:.3g} > {tol}"


# ---------------------------------------------------------------------------
# Chunked-prefill equivalence (cache banks + decode tokens)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,chunks", [
    ("bert_base", (1, 4, 5, 16)),
    ("glm4_9b", (3, 8)),
])
def test_chunked_prefill_seeds_identical_cache_bank(name, chunks):
    """Deterministic sweep of the equivalence property: every chunk size
    seeds the same bank as the whole-prompt prefill (float atol 2e-6)."""
    cfg = _smoke_cfg(name)
    params = _params(cfg)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)
    want = _whole_banks(cfg, params, prompt)
    for chunk in chunks:
        got = _chunked_banks(cfg, params, prompt, chunk, capacity=16)
        _assert_banks_match(got, want, CHUNK_FLOAT_TOL)


def test_chunked_prefill_cache_bank_npe_mode():
    """NPE mode (quantized MMU + PWL NVU on both sides): chunked and
    whole-prompt banks agree to the conformance suite's 5e-3."""
    from conftest import NPE_TOL

    cfg = _smoke_cfg("bert_base")
    params = _params(cfg)
    npe_cfg = cfg.with_npe(quant_bits=16)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
    want = _whole_banks(cfg, params, prompt, npe_cfg=npe_cfg)
    got = _chunked_banks(cfg, params, prompt, 4, capacity=12,
                         npe_cfg=npe_cfg)
    _assert_banks_match(got, want, NPE_TOL)


def test_chunked_prefill_last_logits_npe_mode():
    """Int8 NPE mode, glm4: whole and chunked prefill give the last
    prompt row the same logits to the conformance suite's 5e-3, since
    every MMU row is quantized on its own whatever tile it arrives in."""
    from conftest import NPE_TOL

    cfg = _smoke_cfg("glm4_9b")
    params = _params(cfg)
    npe_cfg = cfg.with_npe(quant_bits=8)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)
    want_banks, want = _whole_prefill(cfg, params, prompt, npe_cfg=npe_cfg)
    got_banks, got = _chunked_prefill(cfg, params, prompt, 4, capacity=16,
                                      npe_cfg=npe_cfg)
    _assert_banks_match(got_banks, want_banks, NPE_TOL)
    err = float(np.abs(got - want).max())
    assert err <= NPE_TOL, err


def _engine_tokens(cfg, params, prompts, chunk, capacity=16, gen=4):
    import jax

    eng = NPEEngine(cfg, HW, slots=2, capacity=capacity,
                    max_new_tokens=gen, params=params,
                    prefill_chunk=chunk)
    for p in prompts:
        eng.submit(p)
    with jax.disable_jit():
        stats = eng.run()
    return {r.rid: r.generated for r in stats.requests}


def test_chunked_engine_decode_tokens_identical():
    """The strict functional gate: a chunked engine generates the SAME
    decode tokens as the whole-prompt engine (numeric float mode)."""
    cfg = _smoke_cfg("bert_base")
    params = _params(cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
               for s in (5, 9, 3)]
    want = _engine_tokens(cfg, params, prompts, None)
    for chunk in (1, 4):
        assert _engine_tokens(cfg, params, prompts, chunk) == want, chunk


@settings(max_examples=5, deadline=None, derandomize=True)
@given(st.integers(1, 13), st.integers(1, 13))
def test_chunked_prefill_equivalence_property(seq, chunk):
    """Hypothesis form of the equivalence gate: random (S, chunk)."""
    chunk = min(chunk, seq)
    cfg = _smoke_cfg("bert_base")
    params = _params(cfg)
    rng = np.random.default_rng(seq * 31 + chunk)
    prompt = rng.integers(0, cfg.vocab_size, size=seq).astype(np.int32)
    want = _whole_banks(cfg, params, prompt)
    got = _chunked_banks(cfg, params, prompt, chunk, capacity=16)
    _assert_banks_match(got, want, CHUNK_FLOAT_TOL)


# ---------------------------------------------------------------------------
# Engine conservation invariants
# ---------------------------------------------------------------------------

def _run_checked(cfg, n_requests, slots, chunk, seed, capacity=24, gen=6):
    """Step an engine to completion, asserting the serving invariants
    after every step; returns its stats."""
    from repro.data.pipeline import SyntheticRequests

    eng = NPEEngine(cfg, HW, slots=slots, capacity=capacity,
                    max_new_tokens=gen, prefill_chunk=chunk)
    reqs = SyntheticRequests(cfg.vocab_size, max_prompt=12, seed=seed)
    for i in range(n_requests):
        eng.submit(reqs.request(i), eos_id=reqs.eos_id(i))
    last = eng.clock.cycles
    while eng.queue or len(eng.pool):
        if not eng.step():
            break
        # charged cycles are monotone in the clock
        assert eng.clock.cycles >= last
        last = eng.clock.cycles
        # no slot serves two live requests: every bound request is live
        # (unfinished) and bound exactly once
        live = eng.pool.active()
        rids = [r.rid for _, r in live]
        assert len(rids) == len(set(rids))
        for _, r in live:
            assert not r.done
    stats = eng.stats
    # conservation: every submitted request finished exactly once, and
    # tokens_out is the sum of per-request completions
    assert len(stats.requests) == n_requests
    for r in stats.requests:
        assert r.done and 1 <= len(r.generated) <= r.max_new_tokens
        assert len(r.token_cycles) == len(r.generated)
        assert r.token_cycles == sorted(r.token_cycles)
    tokens_out = sum(len(r.generated) for r in stats.requests)
    assert tokens_out == sum(len(r.token_cycles) for r in stats.requests)
    assert stats.prefills == n_requests
    assert len(eng.pool) == 0
    return stats


@pytest.mark.parametrize("chunk", [None, 1, 4, 64])
def test_engine_conservation_sweep(chunk):
    cfg = _smoke_cfg("bert_base")
    base = _run_checked(cfg, 8, 2, None, seed=0)
    got = _run_checked(cfg, 8, 2, chunk, seed=0)
    # same workload, same completions regardless of chunking
    assert ({r.rid: r.generated for r in got.requests}
            == {r.rid: r.generated for r in base.requests})


@settings(max_examples=8, deadline=None, derandomize=True)
@given(st.integers(1, 10), st.integers(1, 4), st.integers(0, 8),
       st.integers(0, 3))
def test_engine_conservation_property(n_requests, slots, chunk, seed):
    """Hypothesis form: random workload shape x chunk (0 = unchunked)."""
    cfg = _smoke_cfg("bert_base")
    _run_checked(cfg, n_requests, slots, chunk or None, seed)


# ---------------------------------------------------------------------------
# Committed record guard
# ---------------------------------------------------------------------------

def test_npec_disagg_record_is_current():
    """Bit-exact guard on results/npec_disagg_cycles.json (cost-only:
    pure cycle model; regenerate via `python -m benchmarks.run`)."""
    from conftest import assert_cycle_record
    assert_cycle_record("npec_disagg_cycles.json",
                        "npec_disagg_cycles/v1", "npec_disagg")
