"""npec — the NPE compiler: model -> overlay instruction stream.

The paper's headline claim is software-like programmability (§5, §6): the
FPGA bitstream is fixed and every model is *compiled* to an instruction
stream the ICU interprets.  This package is that compile-and-schedule
layer, a four-stage pipeline:

    trace    (repro.npec.trace)    ModelConfig -> graph IR: per-head
             matmul / softmax / norm / activation dataflow with shape and
             dtype metadata, one explicit emitter per model family (bert,
             dense, moe — MoE routing as topk/gather/scatter_slot ops
             with capacity-bounded per-expert matmul streams); both
             prefill graphs (trace_model) and one-token KV-cache decode
             graphs (trace_decode — cache-resident tensors, cache-append,
             pos-masked softmax).
    lower    (repro.npec.lower)    graph IR -> overlay instructions:
             matmuls tiled to the MMU geometry (128 PEs x MAC depth),
             nonlinearities expanded to NVU microprograms with VLIW issue
             bundles (1 LSU + 3 VCU + 1 SCU, §6.1) and the 32 vector
             registers allocated by linear scan.
    schedule (repro.npec.schedule) greedy earliest-start list scheduling
             over the per-unit timelines; the softmax/matmul overlap of
             §7.2.1 emerges from the dependency structure.
    exec     (repro.npec.exec)     functional interpretation of a compiled
             program against the NVU / quant engines, validating every
             instruction stream end-to-end against the jnp model.

Entry points:
    compile_model(cfg, seq, hw, ...)    trace + lower a registered model
                                        (prefill).
    compile_decode(cfg, T, hw, ...)     trace + lower a one-token decode
                                        step over a KV cache of capacity T
                                        (batch=B: one merged B-slot stream,
                                        B-row MMU tiles, per-slot banks).
    compile_prefill(cfg, S, hw, ...)    serving prefill: causal pass with
                                        kv exports that seed a decode
                                        slot's cache banks.
    compile_bert_shape(hw, shape, ...)  dims-only BERT path used as the
                                        `backend="npec"` of core.cycles.
    compile_decode_bert_shape(...)      dims-only decode step — the cost
                                        model behind autoregressive
                                        tokens/sec tables.
    greedy_schedule / issue_order       schedule a CompiledProgram
                                        (whole-op DAG model).
    stream_schedule                     tile-granular streaming schedule
                                        (the paper's own latency model,
                                        with per-stall budgets).
    execute / DecodeSession             run it numerically (DecodeSession
                                        carries KV-cache state across
                                        steps; batched-slot streams get
                                        per-slot pos/reset/load_slot).
    prepare_weights                     quantize a tree's MMU weights
                                        once, for every later run.

The serving layer over all of this lives in repro.npec.runtime
(`NPEEngine`: continuous batching + cycle-clocked latency; docs/serving.md),
and the multi-overlay fleet simulator in repro.npec.fleet (`NPEFleet`:
shared admission queue + replicate/expert/pipeline sharding with
inter-overlay transfers charged as MRU/MWU `make_transfer` instructions;
docs/fleet.md).

Cross-checks: the compiled BERT-base stream matches the hand-built program
in `core.cycles.build_encoder_program` on per-unit instruction counts and
scheduled latency (<1%), its functional execution matches the jnp BERT
encoder, and decode-stream rollouts match models/{transformer,bert}
decode_step — see tests/test_npec.py and tests/test_npec_decode.py.
Reference docs: docs/isa.md (the overlay ISA) and docs/compiler.md (the
pipeline).
"""
from __future__ import annotations

from typing import Optional

from repro.config import ModelConfig
from repro.core.overlay import NPEHardware
from repro.npec.ir import Graph, GraphBuilder, Node
from repro.npec.lower import (CompiledProgram, LoweredInstr, lower,
                              make_transfer, nvu_microprogram, tile_matmul)
from repro.npec.schedule import (greedy_schedule, issue_order, schedule_for,
                                 stream_schedule, transfer_cycles)
from repro.npec.trace import (CompileError, moe_capacity, trace_bert_shape,
                              trace_decode, trace_decode_bert_shape,
                              trace_model, trace_moe_block, trace_prefill,
                              trace_prefill_slice_shape)
from repro.npec.exec import (DecodeSession, ExecResult, execute,
                             prepare_weights)


def compile_model(cfg: ModelConfig, seq: int, hw: Optional[NPEHardware] = None,
                  *, bits: int = 16, nvu_source: str = "paper",
                  layers: Optional[int] = None,
                  include_embed: bool = True) -> CompiledProgram:
    """Trace `cfg` at sequence length `seq` and lower it to the overlay."""
    hw = hw if hw is not None else NPEHardware()
    return lower(trace_model(cfg, seq, layers=layers,
                             include_embed=include_embed),
                 hw, bits=bits, nvu_source=nvu_source)


def compile_bert_shape(hw: NPEHardware, shape, bits: int,
                       *, nvu_source: str = "paper",
                       layers: int = 1) -> CompiledProgram:
    """Compile a raw `core.cycles.BertShape` encoder stack (dims only)."""
    return lower(trace_bert_shape(shape, layers=layers), hw, bits=bits,
                 nvu_source=nvu_source)


def compile_decode(cfg: ModelConfig, cache_len: int,
                   hw: Optional[NPEHardware] = None, *, bits: int = 16,
                   nvu_source: str = "paper", layers: Optional[int] = None,
                   include_embed: bool = True,
                   batch: int = 1, window: bool = False) -> CompiledProgram:
    """Trace one decode step of `cfg` over a KV cache of capacity
    `cache_len` and lower it to the overlay.  Execute statefully with
    `DecodeSession`.  batch=B compiles the merged B-slot stream the
    serving engine (repro.npec.runtime) clocks: B-row projection tiles,
    per-slot cache banks, a (B,) pos vector.  window=True compiles the
    ring (sliding-window) variant: appends wrap at `cache_len`, positions
    grow unbounded, the QK^T tile stays banded at `cache_len` keys (for
    "sliding"-attention configs cache_len must equal cfg.window)."""
    hw = hw if hw is not None else NPEHardware()
    return lower(trace_decode(cfg, cache_len, layers=layers,
                              include_embed=include_embed, batch=batch,
                              window=window),
                 hw, bits=bits, nvu_source=nvu_source)


def compile_prefill(cfg: ModelConfig, seq: int,
                    hw: Optional[NPEHardware] = None, *, bits: int = 16,
                    nvu_source: str = "paper", layers: Optional[int] = None,
                    include_embed: bool = True,
                    cache_len: Optional[int] = None,
                    window: bool = False) -> CompiledProgram:
    """Trace + lower the *serving prefill* stream for a `seq`-token
    prompt: causal, ends at the logits head, and exports each kv head's
    (S, head_dim) k/v rows (`Graph.kv_exports`) so `DecodeSession.
    load_slot` can seed a decode slot from one executed pass.

    cache_len=T compiles one *chunked-prefill slice* instead: `seq` prompt
    rows appended into (T, head_dim) cache banks with a row-masked causal
    softmax over the updated cache; `NPEEngine(prefill_chunk=...)` runs
    ceil(S/chunk) of these, carrying cache_updates between them.

    window=True marks a *windowed-engine* prefill (ring decode banks):
    the prompt must fit cfg.window for "sliding"-attention configs, whose
    gate it lifts — a causal S <= W prefill is exactly the sliding model's
    own computation."""
    hw = hw if hw is not None else NPEHardware()
    return lower(trace_prefill(cfg, seq, layers=layers,
                               include_embed=include_embed,
                               cache_len=cache_len, window=window),
                 hw, bits=bits, nvu_source=nvu_source)


def compile_prefill_slice_shape(hw: NPEHardware, shape, cache_len: int,
                                rows: int, bits: int, *,
                                nvu_source: str = "paper",
                                layers: int = 1) -> CompiledProgram:
    """Compile a dims-only chunked-prefill slice for a `core.cycles`
    BertShape — the cost model behind the per-chunk stall bound
    (`core.cycles.chunked_prefill_cycles`)."""
    return lower(trace_prefill_slice_shape(shape, cache_len, rows,
                                           layers=layers),
                 hw, bits=bits, nvu_source=nvu_source)


def compile_decode_bert_shape(hw: NPEHardware, shape, cache_len: int,
                              bits: int, *, nvu_source: str = "paper",
                              layers: int = 1, batch: int = 1,
                              window: bool = False) -> CompiledProgram:
    """Compile a dims-only decode step for a `core.cycles.BertShape` —
    the per-step cost model behind autoregressive serving tables.
    batch=B merges B decode slots into one stream (B-row MMU tiles);
    window=True makes the banks rings (banded `cache_len`-key QK^T)."""
    return lower(trace_decode_bert_shape(shape, cache_len, layers=layers,
                                         batch=batch, window=window),
                 hw, bits=bits, nvu_source=nvu_source)
