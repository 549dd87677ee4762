"""Fleet-simulator validation (repro.npec.fleet, docs/fleet.md).

Five gates:
  * bit-equality — a fleet of 1 replicate overlay reproduces a lone
    `NPEEngine.run()` exactly: same generated tokens, same per-request
    cycle stamps, same makespan (the ISSUE acceptance bar: N=1 replicate
    must reproduce the single-engine serve record's numbers);
  * conservation at N in {2, 4} — every submitted request completes
    exactly once on exactly one overlay, no slot leaks, and the summed
    per-overlay busy cycles (+ itemized transfers) are at least the
    monolithic single-overlay charge for the same workload;
  * partitioning invariants — pipeline stages cover every instruction
    exactly once with transfers only at stage boundaries; expert plans
    cover every per-expert instruction exactly once with dispatch/combine
    crossings of C x E_r rows per remote;
  * Poisson determinism — `SyntheticRequests.arrival_cycles` is seeded,
    sorted, and rate-scaled;
  * tensor parallelism — fleet-of-1 is the identity plan (bit-equal to
    the lone engine), N in {2, 4} reproduce the replicate fleet's token
    streams at strictly lower per-request latency with the all-reduce
    itemized, and carved column shards reassemble to the unsharded
    projection exactly (hypothesis property);
  * cycle regression — recomputing the fleet table reproduces
    results/npec_fleet_cycles.json exactly (cost-only: the record is
    pure cycle model, regenerate via `python -m benchmarks.run` if the
    compiler or fleet changed).
"""
import dataclasses

import numpy as np
import pytest

from repro import npec
from repro.core.overlay import NPEHardware
from repro.data.pipeline import SyntheticRequests
from hypothesis import given, settings, strategies as st
from repro.npec.fleet import (NPEFleet, partition_expert,
                              partition_pipeline, partition_tensor)
from repro.npec.runtime import NPEEngine

HW = NPEHardware(vrwidth=1024)


def _smoke_cfg(name="bert_base"):
    from repro.configs import get_config
    return dataclasses.replace(get_config(name, smoke=True),
                               dtype="float32")


def _submit_workload(submit, n=8, max_prompt=12, vocab=1000):
    reqs = SyntheticRequests(vocab, max_prompt=max_prompt)
    for i in range(n):
        submit(reqs.request(i), reqs.eos_id(i))


# ---------------------------------------------------------------------------
# Fleet-of-1 replicate == lone engine, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["bert_base", "glm4_9b"])
def test_fleet_of_one_bit_equal_to_lone_engine(name):
    cfg = _smoke_cfg(name)
    lone = NPEEngine(cfg, HW, slots=2, capacity=24, max_new_tokens=6)
    _submit_workload(lambda p, e: lone.submit(p, eos_id=e),
                     vocab=cfg.vocab_size)
    ls = lone.run()

    fleet = NPEFleet(cfg, HW, overlays=1, shard="replicate", slots=2,
                     capacity=24, max_new_tokens=6)
    _submit_workload(lambda p, e: fleet.submit(p, eos_id=e),
                     vocab=cfg.vocab_size)
    fs = fleet.run()

    assert fs.makespan_cycles == ls.total_cycles
    assert fs.transfer_cycles == 0
    lr = {r.rid: r for r in ls.requests}
    fr = {r.rid: r for r in fs.requests}
    assert set(lr) == set(fr)
    for rid, lreq in lr.items():
        freq = fr[rid]
        assert freq.generated == lreq.generated
        assert (freq.submit_cycle, freq.admit_cycle,
                freq.first_token_cycle, freq.finish_cycle) == \
               (lreq.submit_cycle, lreq.admit_cycle,
                lreq.first_token_cycle, lreq.finish_cycle)
    # engine-level stats line up too (same steps, same prefills)
    es = fleet.engines[0].stats
    assert (es.decode_steps, es.prefills, es.total_cycles) == \
           (ls.decode_steps, ls.prefills, ls.total_cycles)


def test_fleet_of_one_report_matches_engine_report():
    """The fleet report's latency split is derived from the same request
    stamps the engine records, so percentiles agree exactly."""
    cfg = _smoke_cfg()
    fleet = NPEFleet(cfg, HW, overlays=1, shard="replicate", slots=2,
                     capacity=24, max_new_tokens=6)
    _submit_workload(lambda p, e: fleet.submit(p, eos_id=e),
                     vocab=cfg.vocab_size)
    rep = fleet.run().report()
    erep = fleet.engines[0].stats.report()
    for k in ("p50_ms", "p99_ms", "queue_wait_p50_ms",
              "queue_wait_p99_ms", "service_p50_ms", "service_p99_ms"):
        assert rep[k] == erep[k], k
    assert rep["tokens"] == erep["generated_tokens"]


# ---------------------------------------------------------------------------
# Conservation at N in {2, 4}
# ---------------------------------------------------------------------------

def _mono_busy(cfg, **kw):
    fleet = NPEFleet(cfg, HW, overlays=1, shard="replicate", **kw)
    _submit_workload(lambda p, e: fleet.submit(p, eos_id=e), n=12,
                     vocab=cfg.vocab_size)
    stats = fleet.run()
    return sum(stats.busy_cycles), fleet


@pytest.mark.parametrize("shard", ["replicate", "pipeline"])
@pytest.mark.parametrize("n", [2, 4])
def test_fleet_conservation(shard, n):
    # pipeline needs >= n layer groups; bump the smoke stack to 4 layers
    cfg = dataclasses.replace(_smoke_cfg("bert_base"), num_layers=4)
    kw = dict(slots=2, capacity=24, max_new_tokens=6)
    mono, _ = _mono_busy(cfg, **kw)

    fleet = NPEFleet(cfg, HW, overlays=n, shard=shard, **kw)
    _submit_workload(lambda p, e: fleet.submit(p, eos_id=e), n=12,
                     vocab=cfg.vocab_size)
    stats = fleet.run()

    # every submitted request completes exactly once
    rids = [r.rid for r in stats.requests]
    assert sorted(rids) == list(range(12))
    assert all(r.done for r in stats.requests)
    assert all(r.admit_cycle >= r.submit_cycle for r in stats.requests)
    assert all(r.finish_cycle > r.admit_cycle for r in stats.requests)
    # no slot leaks: every pool drained, nothing left queued
    for eng in fleet.engines:
        assert len(eng.pool) == 0
    assert len(fleet.queue) == 0
    # sharded/replicated work + transfers can't undercut the monolithic
    # charge for the same workload
    assert sum(stats.busy_cycles) + stats.transfer_cycles >= mono
    if shard == "pipeline":
        assert stats.transfer_cycles > 0


@pytest.mark.parametrize("n", [2, 4])
def test_fleet_expert_conservation(n):
    cfg = _smoke_cfg("granite_moe_1b_a400m")
    seq = 16
    mono_prog = npec.compile_model(cfg, seq, HW, bits=16)
    mono = npec.schedule_for(mono_prog, "streaming")["total_cycles"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (seq,), np.int32)
               for _ in range(6)]

    fleet = NPEFleet(cfg, HW, overlays=n, shard="expert", seq=seq)
    for p in prompts:
        fleet.submit(p)
    stats = fleet.run()

    assert sorted(r.rid for r in stats.requests) == list(range(6))
    assert all(r.done for r in stats.requests)
    assert stats.transfer_cycles > 0
    # per-request: the barriered sharded charge >= the monolithic stream
    assert sum(stats.busy_cycles) + stats.transfer_cycles \
        >= len(prompts) * mono * 0.999   # float schedule rounding
    # homes rotate, so at N>=2 every overlay gets home work
    assert all(b > 0 for b in stats.busy_cycles)


def test_fleet_sharding_beats_monolithic_in_record():
    """ISSUE acceptance: expert/pipeline at N>=2 show aggregate
    tokens/sec gains over the N=1 baseline in the committed record, with
    transfer overhead itemized (nonzero, separate field)."""
    import json
    from pathlib import Path
    rec = json.loads((Path(__file__).parent.parent / "results" /
                      "npec_fleet_cycles.json").read_text())
    rows = {(r["family"], r["shard"], r["overlays"], r["rate_rps"]): r
            for r in rec["rows"]}
    bert1 = rows[("bert", "replicate", 1, None)]
    for n in (2, 4):
        pipe = rows[("bert", "pipeline", n, None)]
        assert pipe["tok_s"] > bert1["tok_s"]
        assert pipe["transfer_cycles"] > 0
    moe1 = rows[("moe", "expert", 1, None)]
    for n in (2, 4):
        exp = rows[("moe", "expert", n, None)]
        assert exp["tok_s"] > moe1["tok_s"]
        assert exp["transfer_cycles"] > 0


# ---------------------------------------------------------------------------
# Partitioning invariants
# ---------------------------------------------------------------------------

def test_partition_pipeline_covers_stream_once():
    cfg = _smoke_cfg("bert_base")
    compiled = npec.compile_decode(cfg, 24, HW, bits=16, batch=2)
    plan = partition_pipeline(compiled, 2, rows=2)
    n_xfer = sum(1 for p in plan.stages for i in p.instrs
                 if i.meta.get("xfer"))
    n_instrs = sum(len(p.instrs) for p in plan.stages) - n_xfer
    assert n_instrs == len(compiled.instrs)
    assert n_xfer == 2                      # one send + one recv boundary
    # transfers charge `rows` cycles each at the 1-row/cycle convention
    assert npec.transfer_cycles(plan.stages[0]) == 2
    assert npec.transfer_cycles(plan.stages[1]) == 2
    # layer groups are contiguous and cover all layers
    flat = [l for g in plan.layer_groups for l in g]
    assert flat == sorted(flat)
    # per-unit busy is conserved exactly once transfers are itemized out
    mono_busy = compiled.busy_by_unit()
    split_busy = {}
    for p in plan.stages:
        for ins in p.instrs:
            if ins.meta.get("xfer"):
                continue
            split_busy[ins.unit] = split_busy.get(ins.unit, 0) + ins.cycles
    assert split_busy == mono_busy


def test_partition_pipeline_rejects_too_many_stages():
    cfg = _smoke_cfg("bert_base")
    compiled = npec.compile_decode(cfg, 24, HW, bits=16, batch=2)
    with pytest.raises(ValueError):
        partition_pipeline(compiled, cfg.num_layers + 1, rows=2)


def test_partition_expert_crossings():
    """Dispatch/combine crossings charge C x E_r rows per remote overlay
    — the worked example in docs/fleet.md."""
    cfg = _smoke_cfg("granite_moe_1b_a400m")
    seq = 16
    compiled = npec.compile_model(cfg, seq, HW, bits=16)
    cap = npec.moe_capacity(cfg, seq)
    E = cfg.moe.num_experts
    n = 2
    plan = partition_expert(compiled, n)
    assert plan.capacity == cap
    expert_phases = [ph for ph in plan.phases if len(ph.tasks) > 1
                     or ph.tasks[0].rel != 0]
    # every expert instruction lands exactly once
    n_expert_instrs = sum(
        sum(1 for i in t.prog.instrs if not i.meta.get("xfer"))
        for ph in expert_phases for t in ph.tasks)
    from repro.npec.fleet.partition import _EXPERT_RE
    assert n_expert_instrs == sum(
        1 for i in compiled.instrs if _EXPERT_RE.match(i.tag))
    # each remote task recv+send = 2 x C x E_r rows
    for ph in expert_phases:
        for t in ph.tasks:
            if t.rel == 0:
                assert t.xfer_rows == 0
            else:
                e_r = E // n
                assert t.xfer_rows == 2 * cap * e_r
    # single-overlay plan has no crossings at all
    assert partition_expert(compiled, 1).transfer_rows == 0


def test_fleet_rejects_mismatched_family():
    bert = _smoke_cfg("bert_base")
    moe = _smoke_cfg("granite_moe_1b_a400m")
    with pytest.raises(ValueError):
        NPEFleet(bert, HW, overlays=2, shard="expert")
    with pytest.raises(ValueError):
        NPEFleet(moe, HW, overlays=2, shard="replicate", slots=2,
                 capacity=24)


# ---------------------------------------------------------------------------
# Poisson arrivals
# ---------------------------------------------------------------------------

def test_arrival_cycles_deterministic_and_rate_scaled():
    r1 = SyntheticRequests(1000, max_prompt=8, rate_rps=10.0,
                           clock_hz=200e6)
    r2 = SyntheticRequests(1000, max_prompt=8, rate_rps=10.0,
                           clock_hz=200e6)
    a, b = r1.arrival_cycles(64), r2.arrival_cycles(64)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0)
    # mean inter-arrival ~ clock_hz / rate (law of large numbers, seeded)
    mean_gap = float(a[-1]) / 64
    assert 0.5 * 200e6 / 10.0 < mean_gap < 2.0 * 200e6 / 10.0
    # no rate -> the legacy everything-at-t0 workload
    assert np.all(SyntheticRequests(1000, max_prompt=8)
                  .arrival_cycles(8) == 0)
    # doubling the rate halves the arrival span (same exponential draws)
    fast = SyntheticRequests(1000, max_prompt=8, rate_rps=20.0,
                             clock_hz=200e6).arrival_cycles(64)
    assert abs(float(fast[-1]) * 2 - float(a[-1])) <= 64


def test_fleet_queue_wait_drops_with_overlays():
    """Under a loaded Poisson arrival process, adding overlays must cut
    queue-wait p99 — the fleet's reason to exist."""
    cfg = _smoke_cfg("bert_base")
    reqs = SyntheticRequests(cfg.vocab_size, max_prompt=12,
                             rate_rps=4000.0, clock_hz=HW.clock_hz)
    arrive = reqs.arrival_cycles(12)
    reports = {}
    for n in (1, 2):
        fleet = NPEFleet(cfg, HW, overlays=n, shard="replicate", slots=2,
                         capacity=24, max_new_tokens=6)
        for i in range(12):
            fleet.submit(reqs.request(i), eos_id=reqs.eos_id(i),
                         arrival_cycle=int(arrive[i]))
        reports[n] = fleet.run().report()
    assert reports[2]["queue_wait_p99_ms"] < reports[1]["queue_wait_p99_ms"]


# ---------------------------------------------------------------------------
# Chunked prefill in the fleet
# ---------------------------------------------------------------------------

def test_fleet_of_one_chunked_bit_equal_to_lone_chunked_engine():
    """The fleet-of-1 bit-equality gate extends to the chunked-prefill
    path: replicate N=1 with prefill_chunk reproduces a lone chunked
    engine exactly."""
    cfg = _smoke_cfg("bert_base")
    lone = NPEEngine(cfg, HW, slots=2, capacity=24, max_new_tokens=6,
                     prefill_chunk=4)
    _submit_workload(lambda p, e: lone.submit(p, eos_id=e),
                     vocab=cfg.vocab_size)
    ls = lone.run()

    fleet = NPEFleet(cfg, HW, overlays=1, shard="replicate", slots=2,
                     capacity=24, max_new_tokens=6, prefill_chunk=4)
    _submit_workload(lambda p, e: fleet.submit(p, eos_id=e),
                     vocab=cfg.vocab_size)
    fs = fleet.run()

    assert fs.makespan_cycles == ls.total_cycles
    lr = {r.rid: r for r in ls.requests}
    fr = {r.rid: r for r in fs.requests}
    assert set(lr) == set(fr)
    for rid, lreq in lr.items():
        freq = fr[rid]
        assert freq.generated == lreq.generated
        assert freq.token_cycles == lreq.token_cycles
        assert (freq.submit_cycle, freq.admit_cycle,
                freq.first_token_cycle, freq.finish_cycle) == \
               (lreq.submit_cycle, lreq.admit_cycle,
                lreq.first_token_cycle, lreq.finish_cycle)


# ---------------------------------------------------------------------------
# Prefill/decode disaggregation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [None, 4])
def test_fleet_prefill_decode_conserves_tokens_vs_replicate(chunk):
    """ISSUE acceptance: a disaggregated fleet emits token streams
    identical to the replicate fleet for the same seed, conserves every
    request, and itemizes the KV-shipping transfer cycles."""
    cfg = _smoke_cfg("bert_base")
    reqs = SyntheticRequests(cfg.vocab_size, max_prompt=12, rate_rps=8.0,
                             clock_hz=HW.clock_hz)
    arrive = reqs.arrival_cycles(8)

    def run(shard):
        fleet = NPEFleet(cfg, HW, overlays=2, shard=shard, slots=2,
                         capacity=24, max_new_tokens=6,
                         prefill_chunk=chunk, prefill_overlays=1)
        for i in range(8):
            fleet.submit(reqs.request(i), eos_id=reqs.eos_id(i),
                         arrival_cycle=int(arrive[i]))
        return fleet, fleet.run()

    rfleet, rep = run("replicate")
    dfleet, dis = run("prefill_decode")

    assert ({r.rid: r.generated for r in dis.requests}
            == {r.rid: r.generated for r in rep.requests})
    assert sorted(r.rid for r in dis.requests) == list(range(8))
    assert all(r.done for r in dis.requests)
    assert all(r.admit_cycle >= r.submit_cycle for r in dis.requests)
    assert dis.tokens == rep.tokens
    assert dis.prefills == rep.prefills == 8
    # the KV ship is itemized: kv_rows_per_token rows per prompt token,
    # charged MWU out of the prefill overlay AND MRU into the decode one
    kv = dfleet.disagg_plan.kv_rows_per_token
    expect = 2 * kv * sum(len(r.prompt) for r in dis.requests)
    assert kv > 0 and dis.transfer_cycles == expect
    assert rep.transfer_cycles == 0
    for eng in dfleet.engines:
        assert len(eng.pool) == 0


def test_partition_prefill_decode_plan():
    """The KV plan sizes transfers from Graph.kv_exports and rejects
    streams without them."""
    from repro.npec.fleet import partition_prefill_decode
    cfg = _smoke_cfg("bert_base")
    prefill = npec.compile_prefill(cfg, 8, HW, bits=16)
    plan = partition_prefill_decode(prefill, prefill_overlays=1,
                                    decode_overlays=1)
    assert plan.kv_rows_per_token == len(prefill.graph.kv_exports)
    send, recv = plan.send_prog(8), plan.recv_prog(8)
    assert npec.transfer_cycles(send) == plan.kv_rows_per_token * 8
    assert npec.transfer_cycles(recv) == plan.kv_rows_per_token * 8
    assert send is plan.send_prog(8)                  # memoized
    # a model stream (no kv exports) is rejected with a pointer
    model = npec.compile_model(cfg, 8, HW, bits=16)
    with pytest.raises(ValueError):
        partition_prefill_decode(model, prefill_overlays=1,
                                 decode_overlays=1)
    with pytest.raises(ValueError):
        NPEFleet(cfg, HW, overlays=2, shard="prefill_decode", slots=2,
                 capacity=24, prefill_overlays=2)


# ---------------------------------------------------------------------------
# Tensor parallelism (column-carved streams + cycle-charged all-reduce)
# ---------------------------------------------------------------------------

def _tensor_cfg():
    """Smoke bert with 4 kv heads so N=4 divides (the stock smoke shrink
    keeps 2 kv groups, which only divides across 2 overlays)."""
    return dataclasses.replace(_smoke_cfg("bert_base"), num_kv_heads=4)


def test_fleet_of_one_tensor_bit_equal_to_lone_engine():
    """ISSUE acceptance: a tensor fleet of 1 is the identity plan —
    same tokens, same per-request cycle stamps, same makespan as a lone
    `NPEEngine.run()`, zero transfers."""
    cfg = _smoke_cfg("bert_base")
    lone = NPEEngine(cfg, HW, slots=2, capacity=24, max_new_tokens=6)
    _submit_workload(lambda p, e: lone.submit(p, eos_id=e),
                     vocab=cfg.vocab_size)
    ls = lone.run()

    fleet = NPEFleet(cfg, HW, overlays=1, shard="tensor", slots=2,
                     capacity=24, max_new_tokens=6)
    _submit_workload(lambda p, e: fleet.submit(p, eos_id=e),
                     vocab=cfg.vocab_size)
    fs = fleet.run()

    assert fs.makespan_cycles == ls.total_cycles
    assert fs.transfer_cycles == 0
    lr = {r.rid: r for r in ls.requests}
    fr = {r.rid: r for r in fs.requests}
    assert set(lr) == set(fr)
    for rid, lreq in lr.items():
        freq = fr[rid]
        assert freq.generated == lreq.generated
        assert (freq.submit_cycle, freq.admit_cycle,
                freq.first_token_cycle, freq.finish_cycle) == \
               (lreq.submit_cycle, lreq.admit_cycle,
                lreq.first_token_cycle, lreq.finish_cycle)


@pytest.mark.parametrize("n", [2, 4])
def test_tensor_fleet_conserves_tokens_vs_replicate(n):
    """ISSUE acceptance: the tensor fleet emits token streams identical
    to the replicate fleet for the same workload — only cycles move."""
    cfg = _tensor_cfg()

    def run(shard):
        fleet = NPEFleet(cfg, HW, overlays=n, shard=shard, slots=2,
                         capacity=24, max_new_tokens=6)
        _submit_workload(lambda p, e: fleet.submit(p, eos_id=e),
                         vocab=cfg.vocab_size)
        return fleet, fleet.run()

    _, rep = run("replicate")
    tfleet, ten = run("tensor")
    assert ({r.rid: r.generated for r in ten.requests}
            == {r.rid: r.generated for r in rep.requests})
    assert sorted(r.rid for r in ten.requests) == list(range(8))
    assert all(r.done for r in ten.requests)
    assert ten.tokens == rep.tokens
    assert ten.transfer_cycles > 0
    assert rep.transfer_cycles == 0
    for eng in tfleet.engines:
        assert len(eng.pool) == 0


@pytest.mark.parametrize("n", [2, 4])
def test_tensor_fleet_conservation(n):
    """Every overlay's fleet clock is fully accounted: charged compute +
    itemized transfers + idle == makespan, on every shard timeline."""
    cfg = _tensor_cfg()
    fleet = NPEFleet(cfg, HW, overlays=n, shard="tensor", slots=2,
                     capacity=24, max_new_tokens=6)
    _submit_workload(lambda p, e: fleet.submit(p, eos_id=e), n=12,
                     vocab=cfg.vocab_size)
    stats = fleet.run()

    assert sorted(r.rid for r in stats.requests) == list(range(12))
    assert all(r.done for r in stats.requests)
    assert len(fleet.queue) == 0
    make = stats.makespan_cycles
    for tl in fleet.timelines:
        compute, xfer = tl.busy - tl.xfer, tl.xfer
        idle = make - tl.busy
        assert compute > 0 and xfer > 0 and idle >= 0
        assert compute + xfer + idle == make
    assert stats.transfer_cycles == sum(tl.xfer for tl in fleet.timelines)


def test_tensor_latency_drops_with_overlays():
    """ISSUE acceptance at smoke scale: carving every projection across
    N overlays makes each request strictly faster end to end."""
    cfg = _tensor_cfg()
    reqs = SyntheticRequests(cfg.vocab_size, max_prompt=12)
    reports = {}
    for n in (1, 2, 4):
        fleet = NPEFleet(cfg, HW, overlays=n, shard="tensor", slots=2,
                         capacity=24, max_new_tokens=6)
        for i in range(4):
            fleet.submit(reqs.request(i), eos_id=reqs.eos_id(i))
        reports[n] = fleet.run().report()
    assert (reports[4]["service_p50_ms"] < reports[2]["service_p50_ms"]
            < reports[1]["service_p50_ms"])
    assert (reports[4]["p50_ms"] < reports[2]["p50_ms"]
            < reports[1]["p50_ms"])


def test_partition_tensor_covers_heads_once_and_syncs():
    """Per-head work lands on exactly one shard; every shard closes the
    attention-output / FFN-down / logits boundaries with 2 x rows x
    (n-1) itemized transfer rows; the critical shard beats the
    monolithic stream."""
    from repro.npec.fleet.partition import _HEAD_RE, _KV_RE
    cfg = _smoke_cfg("bert_base")
    compiled = npec.compile_decode(cfg, 24, HW, bits=16, batch=2)
    n = 2
    plan = partition_tensor(compiled, n)
    assert plan.overlays == n and plan.rows == 2
    # attn.out + ffn down per layer, plus the logits all-gather
    assert plan.boundaries == 2 * cfg.num_layers + 1

    def head_tags(instrs):
        return sorted(i.tag for i in instrs
                      if _HEAD_RE.search(i.tag) or _KV_RE.search(i.tag))

    assert (sorted(t for p in plan.shards for t in head_tags(p.instrs))
            == head_tags(compiled.instrs))
    for p in plan.shards:
        assert npec.transfer_cycles(p) == plan.transfer_rows_per_shard
    assert plan.transfer_rows_per_shard == 2 * 2 * (n - 1) * plan.boundaries
    mono = npec.schedule_for(compiled, "streaming")["total_cycles"]
    crit = max(npec.schedule_for(p, "streaming")["total_cycles"]
               for p in plan.shards)
    assert crit < mono
    # n=1 is the identity plan: the very same program, no boundaries
    one = partition_tensor(compiled, 1)
    assert one.shards[0] is compiled and one.boundaries == 0


def test_tensor_rejects_indivisible_head_counts():
    cfg = _smoke_cfg("bert_base")
    compiled = npec.compile_decode(cfg, 24, HW, bits=16, batch=2)
    with pytest.raises(ValueError, match="head"):
        partition_tensor(compiled, 3)          # 4 heads across 3 overlays
    with pytest.raises(ValueError):
        partition_tensor(compiled, 0)
    with pytest.raises(ValueError, match="divide"):
        # the stock smoke shrink keeps 2 kv groups: 2 % 4 != 0
        NPEFleet(cfg, HW, overlays=4, shard="tensor", slots=2,
                 capacity=24, max_new_tokens=6)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 6), st.integers(1, 4),
       st.integers(1, 12), st.sampled_from([2, 4]))
def test_tensor_column_shards_reassemble(seed, rows, kmul, m, n):
    """Property: the column shards `shard_tile` charges reassemble to
    the unsharded projection exactly — concatenating the per-shard
    column products gives the full product, and the k-split partial
    sums all-reduce to it (integer matrices make float matmuls exact)."""
    from repro.npec.lower import shard_tile
    rng = np.random.default_rng(seed)
    k = 2 * n * kmul
    x = rng.integers(-8, 8, (rows, k)).astype(np.float64)
    w = rng.integers(-8, 8, (k, m)).astype(np.float64)
    full = x @ w
    # column-parallel (axis="m"): balanced split, concat reassembles
    cols = [shard_tile(HW, rows, k, m, 16, idx=i, of=n, axis="m")["m"]
            for i in range(n)]
    assert sum(cols) == m and max(cols) - min(cols) <= 1
    off, parts = 0, []
    for c in cols:
        parts.append(x @ w[:, off:off + c])
        off += c
    assert np.array_equal(np.concatenate(parts, axis=1), full)
    # row-parallel (axis="k"): the partial sums meet in an all-reduce
    ks = [shard_tile(HW, rows, k, m, 16, idx=i, of=n, axis="k")["k"]
          for i in range(n)]
    assert ks == [k // n] * n
    partials = [x[:, i * (k // n):(i + 1) * (k // n)]
                @ w[i * (k // n):(i + 1) * (k // n), :] for i in range(n)]
    assert np.array_equal(sum(partials), full)


def test_tensor_latency_drops_in_record():
    """ISSUE acceptance: at FULL bert_base scale the committed record
    shows N=2 and N=4 strictly below the N=1 baseline on e2e latency,
    decode-step cycles AND prefill cycles, with the all-reduce transfer
    cycles itemized (nonzero, separate fields)."""
    import json
    from pathlib import Path
    rec = json.loads((Path(__file__).parent.parent / "results" /
                      "npec_tensor_cycles.json").read_text())
    rows = {r["overlays"]: r for r in rec["rows"]}
    base = rows[1]
    assert base["transfer_cycles"] == 0
    assert base["decode_allreduce_cycles"] == 0
    for n in (2, 4):
        r = rows[n]
        assert r["p50_ms"] < base["p50_ms"]
        assert r["service_p50_ms"] < base["service_p50_ms"]
        assert r["decode_step_cycles"] < base["decode_step_cycles"]
        assert r["prefill_cycles"] < base["prefill_cycles"]
        assert r["decode_allreduce_cycles"] > 0
        assert r["prefill_allreduce_cycles"] > 0
        assert r["transfer_cycles"] > 0
    assert rows[4]["p50_ms"] < rows[2]["p50_ms"]
    assert rows[4]["decode_step_cycles"] < rows[2]["decode_step_cycles"]


# ---------------------------------------------------------------------------
# Determinism: same seed + config => byte-identical reports
# ---------------------------------------------------------------------------

def _fleet_report_json(shard, n, cfg, **kw):
    import json
    fleet = NPEFleet(cfg, HW, overlays=n, shard=shard, **kw)
    if shard == "expert":
        rng = np.random.default_rng(3)
        for _ in range(6):
            fleet.submit(rng.integers(0, cfg.vocab_size, (fleet.seq,),
                                      np.int32))
    else:
        reqs = SyntheticRequests(cfg.vocab_size, max_prompt=12,
                                 rate_rps=8.0, clock_hz=HW.clock_hz)
        arrive = reqs.arrival_cycles(8)
        for i in range(8):
            fleet.submit(reqs.request(i), eos_id=reqs.eos_id(i),
                         arrival_cycle=int(arrive[i]))
    return json.dumps(fleet.run().report(), sort_keys=True)


@pytest.mark.parametrize("shard,n", [
    ("replicate", 1), ("replicate", 2), ("replicate", 4),
    ("pipeline", 2), ("pipeline", 4),
    ("expert", 1), ("expert", 2), ("expert", 4),
    ("prefill_decode", 2), ("prefill_decode", 4),
    ("tensor", 1), ("tensor", 2), ("tensor", 4),
])
def test_fleet_report_deterministic_across_runs(shard, n):
    """Same seed + config => byte-identical EngineStats/FleetStats
    reports across two independent runs, for every shard strategy."""
    if shard == "expert":
        cfg = _smoke_cfg("granite_moe_1b_a400m")
        kw = dict(seq=16)
    else:
        cfg = _smoke_cfg("bert_base")
        if shard == "pipeline":
            cfg = dataclasses.replace(cfg, num_layers=4)
        if shard == "tensor":
            cfg = dataclasses.replace(cfg, num_kv_heads=4)
        kw = dict(slots=2, capacity=24, max_new_tokens=6)
        if shard == "prefill_decode":
            kw.update(prefill_chunk=4, prefill_overlays=1)
    assert (_fleet_report_json(shard, n, cfg, **kw)
            == _fleet_report_json(shard, n, cfg, **kw))


# ---------------------------------------------------------------------------
# Cycle-record regression (bit-exact, like the other five records)
# ---------------------------------------------------------------------------

def test_fleet_cycle_record_regression():
    from conftest import assert_cycle_record
    assert_cycle_record("npec_fleet_cycles.json", "npec_fleet_cycles/v1",
                        "npec_fleet")


def test_tensor_cycle_record_regression():
    from conftest import assert_cycle_record
    assert_cycle_record("npec_tensor_cycles.json",
                        "npec_tensor_cycles/v1", "npec_tensor")
