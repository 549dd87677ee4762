"""Batched serving driver (the paper's deployment scenario).

Two backends:

  * ``--backend jnp`` (default): continuous-batching-lite over the jit'd
    decode step — a fixed pool of B decode slots; each admitted request
    gets a REAL prefill pass (one multi-token `decode_step` call on its
    slot's cache slice — not the old token-by-token loop that ran one
    full-batch decode step per prompt token and let concurrent slots'
    zero-token feeds overwrite each other's caches), then decode advances
    all slots one token per step.  Latency is host wall-clock.

  * ``--backend npec``: the compiled-stream serving engine
    (`repro.npec.runtime.NPEEngine`) — ONE batched decode stream with B
    in-stream slots (B-row MMU projection tiles), compiled prefill per
    admitted request, and p50/p99 latency + tokens/sec derived from
    compiled-stream cycle counts at the overlay's 200 MHz — the numbers
    the paper's §3.1 conversational-AI target (10-15 ms/inference) is
    about.  ``--cycle-model`` picks what each step charges: ``streaming``
    (default, `npec.stream_schedule` — tile-granular producer-consumer
    overlap, the paper's own latency model) or ``dag``
    (`npec.greedy_schedule`, whole-op); both are recorded in the report.
    The synthetic workload is EOS-aware: each request samples an EOS
    token id (`SyntheticRequests.eos_id`), so completions are ragged and
    p99 reflects early-stopping requests, not just token budgets.  See
    docs/serving.md; the benchmark table lives in
    results/npec_serve_cycles.json.

``--overlays N`` (with ``--shard {replicate,expert,pipeline,
prefill_decode,tensor}`` and an optional Poisson ``--rate``) lifts the
npec backend to the multi-overlay fleet simulator (`repro.npec.fleet.
NPEFleet`, docs/fleet.md): N overlays pull from a shared admission queue
on a common fleet clock, with expert-/pipeline-/tensor-parallel sharding
and prefill/decode disaggregation charging inter-overlay transfers as
MRU/MWU traffic.  ``--prefill-chunk C`` streams every admitted prompt as
ceil(S/C) causal cache slices (engine and fleet alike — the chunked
single-engine path bounds the decode stall an unchunked admit causes);
``--prefill-overlays P`` sizes the prefill side of a disaggregated
fleet.  N=1 replicate with no rate and no chunking keeps the lone-engine
path bit-identical.  ``--seq-buckets {auto,64,128,...}`` compiles the
decode stream at several capacity buckets and clocks each step at the
smallest one covering the deepest live slot (cache banks migrate at
crossings); ``--window W`` serves with a ring cache that never grows —
the sliding-window families' natural shape (docs/serving.md).

For encoder-only BERT, "serving" is one encoder pass per request batch —
see examples/serve_bert.py, which reproduces the paper's latency table
with the NPE cycle model alongside wall-clock CPU numbers.

Every backend builds ``--arch`` at its published width; ``--smoke`` swaps
in the reduced same-family config and the tiny CI workload.  Full
``glm4_9b`` (the default arch, ~19 GB in bf16) does not fit one 16 GB
TPU v5e: serve it with ``--smoke`` or pick ``--arch bert_base``.

CI smoke: PYTHONPATH=src python -m repro.launch.serve --backend npec --smoke
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import MeshConfig, RunConfig, ShapeConfig
from repro.configs import get_config
from repro.data.pipeline import SyntheticRequests
from repro.launch.compile_cache import init_compilation_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_decode_step
from repro.models import common as cm
from repro.models import registry
from repro.sharding import rules as R


@dataclass
class ServeStats:
    latencies_ms: List[float] = field(default_factory=list)
    tokens: int = 0
    wall: float = 0.0

    def report(self) -> Dict[str, float]:
        lat = np.asarray(self.latencies_ms)
        return {
            "requests": len(lat),
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else 0.0,
            "p99_ms": float(np.percentile(lat, 99)) if len(lat) else 0.0,
            "tokens_per_sec": self.tokens / max(self.wall, 1e-9),
        }


class Server:
    """Decode-slot server for autoregressive models (jnp backend)."""

    def __init__(self, arch: str, smoke: bool = True, batch: int = 4,
                 max_seq: int = 128, npe: bool = False):
        cfg = get_config(arch, smoke=smoke)
        if npe:
            cfg = cfg.with_npe()
        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        self.mesh = make_mesh(MeshConfig(("data", "model"),
                                         (len(jax.devices()), 1)))
        self.rules = R.rules_for("tp")
        run = RunConfig(model=cfg,
                        shape=ShapeConfig("serve", "decode", max_seq, batch),
                        mesh=MeshConfig(("data", "model"),
                                        (len(jax.devices()), 1)))
        key = jax.random.PRNGKey(0)
        with self.mesh, R.active_rules(self.rules):
            self.params = registry.init_params(cfg, key)
            self.decode = jax.jit(build_decode_step(run))
            # prefill: the raw decode_step (logits + cache) on a 1-slot
            # cache slice; jit recompiles per prompt length, as the old
            # per-token path did per shape
            self.prefill = jax.jit(
                lambda p, c, t, pos: registry.decode_step(cfg, p, c, t,
                                                          pos))
            self.cache = cm.init_params(
                registry.cache_specs(cfg, batch, max_seq), key)
        # multi-token prefill through decode_step needs append-at-pos
        # caches everywhere; ring (windowed) caches fall back to a
        # per-token loop on the slot's own cache slice
        self._full_only = set(self.cache) == {"full"}

    def prefill_prompt(self, slot: int, prompt: np.ndarray):
        """Prefill ONE slot with a real prefill pass: the whole prompt
        through `decode_step` (s = len(prompt), positions 0..S-1) on this
        slot's cache slice — one pass per request instead of one
        full-batch zero-token step per prompt token, and no cross-slot
        cache clobbering from the pad feeds."""
        sub = jax.tree.map(lambda a: a[:, slot:slot + 1], self.cache)
        if self._full_only:
            toks = jnp.asarray(prompt, jnp.int32)[None]          # (1, S)
            _, sub = self.prefill(self.params, sub, toks, jnp.int32(0))
        else:
            for t, tok in enumerate(prompt):                     # ring caches
                _, sub = self.prefill(self.params, sub,
                                      jnp.asarray([[tok]], jnp.int32),
                                      jnp.int32(t))
        self.cache = jax.tree.map(
            lambda full, part: full.at[:, slot:slot + 1].set(part),
            self.cache, sub)

    def generate(self, prompts: List[np.ndarray], gen_tokens: int = 8
                 ) -> ServeStats:
        stats = ServeStats()
        t_all = time.time()
        # simple generation round: common position clock per batch
        start = max(len(p) for p in prompts)
        toks = np.zeros((self.batch, 1), np.int32)
        for slot, p in enumerate(prompts[: self.batch]):
            t0 = time.time()
            self.prefill_prompt(slot, p)
            toks[slot, 0] = p[-1]
            stats.latencies_ms.append(1e3 * (time.time() - t0))
        cur = jnp.asarray(toks)
        for i in range(gen_tokens):
            cur, self.cache = self.decode(self.params, self.cache, cur,
                                          jnp.int32(start + i))
            stats.tokens += self.batch
        jax.block_until_ready(cur)
        stats.wall = time.time() - t_all
        return stats


# cycle reports carry full precision (derived math never inherits print
# loss); these keys are rounded HERE, at the presentation layer, so the
# printed lines match the committed table records
_PRINT_ROUND = {"tokens_per_sec": 1, "mmu_row_occupancy": 4}


def _print_report(report: Dict) -> None:
    for k, v in report.items():
        if k in _PRINT_ROUND and isinstance(v, float):
            v = round(v, _PRINT_ROUND[k])
        print(f"  {k}: {v}")


def _make_tracer(args, clock_hz: float):
    """A live cycle tracer when --trace is set, else None (the engine and
    fleet then default to the no-op NULL_TRACER fast path)."""
    if not getattr(args, "trace", None):
        return None
    from repro.npec.obs import Tracer
    return Tracer(clock_hz=clock_hz)


def _npec_outputs(args, tracer, snapshot: Dict) -> None:
    """--json / --trace artifacts from one run's stats snapshot."""
    if getattr(args, "json", None):
        with open(args.json, "w") as f:
            json.dump(snapshot, f, indent=1)
            f.write("\n")
        print(f"wrote json report -> {args.json}")
    if tracer is not None:
        from repro.npec.obs import write_chrome_trace
        write_chrome_trace(tracer, args.trace,
                           report=snapshot["report"],
                           metrics=snapshot["metrics"])
        print(f"wrote trace -> {args.trace} "
              f"({len(tracer.events)} events)")


def run_npec_fleet(args) -> Dict[str, float]:
    """Multi-overlay serving (repro.npec.fleet, docs/fleet.md): N
    overlays pull from a shared admission queue — plain replicas, or one
    model sharded expert-/pipeline-parallel with inter-overlay transfers
    itemized.  Cost-only (the fleet clock is the deliverable); arrivals
    come from the seeded Poisson process when --rate is set."""
    import numpy as np
    from repro.core.overlay import NPEHardware
    from repro.npec.fleet import NPEFleet

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.shard == "tensor" and args.overlays > 1:
        for dim, what in ((cfg.num_heads, "attention heads"),
                          (cfg.num_kv_heads, "kv heads"),
                          (cfg.d_ff, "FFN width (d_ff)")):
            if dim % args.overlays:
                raise SystemExit(
                    f"--shard tensor carves projections column-wise: "
                    f"{what} ({dim}) of {args.arch} must divide evenly "
                    f"across --overlays {args.overlays}")
    hw = NPEHardware(vrwidth=args.vrwidth)
    tracer = _make_tracer(args, hw.clock_hz)
    if args.shard == "expert":
        seq = min(16, args.capacity)
        fleet = NPEFleet(cfg, hw, overlays=args.overlays, shard="expert",
                         bits=args.bits, cycle_model=args.cycle_model,
                         seq=seq, tracer=tracer)
        reqs = SyntheticRequests(cfg.vocab_size, max_prompt=seq,
                                 rate_rps=args.rate, clock_hz=hw.clock_hz)
        arrivals = reqs.arrival_cycles(args.requests)
        rng = np.random.default_rng(11)
        for i in range(args.requests):
            fleet.submit(rng.integers(0, cfg.vocab_size, (seq,), np.int32),
                         arrival_cycle=int(arrivals[i]))
    else:
        max_prompt = args.capacity - args.gen
        fleet = NPEFleet(cfg, hw, overlays=args.overlays, shard=args.shard,
                         slots=args.batch, capacity=args.capacity,
                         max_new_tokens=args.gen, bits=args.bits,
                         cycle_model=args.cycle_model,
                         prefill_chunk=args.prefill_chunk,
                         prefill_overlays=args.prefill_overlays,
                         seq_buckets=args.seq_buckets, window=args.window,
                         tracer=tracer)
        reqs = SyntheticRequests(cfg.vocab_size,
                                 max_prompt=min(16, max_prompt),
                                 rate_rps=args.rate, clock_hz=hw.clock_hz)
        arrivals = reqs.arrival_cycles(args.requests)
        for i in range(args.requests):
            fleet.submit(reqs.request(i), eos_id=reqs.eos_id(i),
                         arrival_cycle=int(arrivals[i]))
    snapshot = fleet.run().snapshot()
    report = snapshot["report"]
    print(f"npec fleet ({args.arch}, {args.overlays} overlays, "
          f"shard={args.shard}, {args.bits}-bit MMU, "
          f"rate={args.rate or 'all-at-t0'}, "
          f"{args.cycle_model} cycle model):")
    _print_report(report)
    _npec_outputs(args, tracer, snapshot)
    return report


def run_npec(args):
    """Compiled-stream serving: NPEEngine over the synthetic workload;
    latency/throughput from compiled-stream cycle counts.

    Returns ``(report, engine)``: the printed report and the engine that
    served it, whose ``stats.requests`` carry the generated tokens and
    whose ``params`` are the weights they were computed from (the seeded
    random init), so a caller can check the served tokens."""
    from repro.core.overlay import NPEHardware
    from repro.npec.runtime import NPEEngine

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.dtype_float32:
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    max_prompt = args.capacity - args.gen
    if max_prompt < 4:
        raise SystemExit(
            f"--capacity ({args.capacity}) must be at least --gen "
            f"({args.gen}) + 4: prompts are 4..{max_prompt} tokens and "
            "every request must fit prompt + generation in its cache slot")
    hw = NPEHardware(vrwidth=args.vrwidth)
    tracer = _make_tracer(args, hw.clock_hz)
    engine = NPEEngine(cfg, hw,
                       slots=args.batch, capacity=args.capacity,
                       max_new_tokens=args.gen, bits=args.bits,
                       npe=args.npe, params=params,
                       cycle_model=args.cycle_model,
                       prefill_chunk=args.prefill_chunk,
                       seq_buckets=args.seq_buckets, window=args.window,
                       tracer=tracer)
    reqs = SyntheticRequests(cfg.vocab_size, max_prompt=min(16, max_prompt))
    for i in range(args.requests):
        # EOS-aware workload: each request carries a sampled stop token,
        # so eviction is ragged rather than budget-only
        engine.submit(reqs.request(i), eos_id=reqs.eos_id(i))
    snapshot = engine.run().snapshot()
    report = snapshot["report"]
    print(f"npec engine ({args.arch}, B={args.batch} slots, "
          f"T={args.capacity}, {args.bits}-bit MMU @ "
          f"{engine.hw.clock_hz / 1e6:.0f} MHz, "
          f"{args.cycle_model} cycle model):")
    _print_report(report)
    _npec_outputs(args, tracer, snapshot)
    return report, engine


def parse_args(argv=None) -> argparse.Namespace:
    """The CLI's arguments, with `--seq-buckets` parsed and the
    `--smoke` workload applied."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4_9b")
    ap.add_argument("--backend", choices=("jnp", "npec"), default="jnp")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=48,
                    help="npec: compiled KV-cache capacity per slot")
    ap.add_argument("--cycle-model", choices=("dag", "streaming"),
                    default="streaming",
                    help="npec: cycles each serving step charges — "
                         "tile-streaming (paper model) or whole-op DAG")
    ap.add_argument("--bits", type=int, default=16)
    ap.add_argument("--vrwidth", type=int, default=1024)
    ap.add_argument("--overlays", type=int, default=1,
                    help="npec: overlays in the fleet (1 = the single-"
                         "engine path, bit-identical to before)")
    ap.add_argument("--shard", choices=("replicate", "expert", "pipeline",
                                        "prefill_decode", "tensor"),
                    default="replicate",
                    help="npec fleet: replicate engines, expert-parallel "
                         "MoE, pipeline-parallel layer groups, "
                         "prefill/decode disaggregation with KV caches "
                         "shipped between overlays, or tensor-parallel "
                         "column-carved projections with cycle-charged "
                         "all-reduces (docs/fleet.md)")
    ap.add_argument("--rate", type=float, default=None,
                    help="npec fleet: Poisson request rate (requests/sec "
                         "at the overlay clock); default all-at-t0")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="npec: stream each prompt as ceil(S/C) causal "
                         "cache slices instead of one whole-prompt "
                         "prefill, bounding the decode stall per step "
                         "(docs/serving.md)")
    ap.add_argument("--prefill-overlays", type=int, default=1,
                    help="npec fleet: dedicated prefill overlays in "
                         "--shard prefill_decode (the remaining overlays "
                         "decode)")
    ap.add_argument("--seq-buckets", default=None,
                    help="npec: length-bucketed decode — 'auto' (64, 128, "
                         "... doubling up to --capacity) or a comma list "
                         "like '64,128,256'; each step clocks the "
                         "smallest bucket covering the deepest live slot, "
                         "migrating cache banks at crossings "
                         "(docs/serving.md)")
    ap.add_argument("--window", type=int, default=None,
                    help="npec: ring (sliding-window) decode at W rows — "
                         "the bucket that never grows; prompts must fit "
                         "W (sliding-attention families: W must equal the "
                         "config's window)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="npec: write a Chrome trace-event/Perfetto JSON "
                         "of the run (cycle-stamped request lifecycles + "
                         "per-overlay unit timelines, docs/"
                         "observability.md); inspect with chrome://"
                         "tracing, ui.perfetto.dev, or python -m "
                         "repro.npec.obs.profile PATH")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="npec: write the cycle report + metrics "
                         "snapshot (counters, families, histograms) as "
                         "structured JSON")
    ap.add_argument("--npe", action="store_true")
    ap.add_argument("--dtype-float32", action="store_true",
                    help="npec: force float32 params (test parity)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config and a tiny workload "
                         "(CI): 2 slots, 4 requests, 4 tokens")
    args = ap.parse_args(argv)
    if args.seq_buckets and args.seq_buckets != "auto":
        args.seq_buckets = tuple(
            int(b) for b in args.seq_buckets.split(","))
    if args.smoke:
        args.batch, args.requests, args.gen = 2, 4, 4
        args.capacity = min(args.capacity, 24)
    return args


def main(argv=None):
    args = parse_args(argv)
    init_compilation_cache()
    if args.backend == "npec":
        if (args.overlays, args.shard, args.rate) == (1, "replicate", None):
            run_npec(args)      # lone-engine path (honors --prefill-chunk)
        else:
            run_npec_fleet(args)
        print("serve OK")
        return
    srv = Server(args.arch, smoke=args.smoke, batch=args.batch,
                 npe=args.npe)
    reqs = SyntheticRequests(srv.cfg.vocab_size, max_prompt=16)
    prompts = [reqs.request(i) for i in range(args.batch)]
    stats = srv.generate(prompts, gen_tokens=args.gen)
    print(stats.report())
    print("serve OK")


if __name__ == "__main__":
    main()
