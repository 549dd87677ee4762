"""Tracers: registered model family -> npec graph IR.

The tracer is the compiler's front end: it walks a `ModelConfig` and emits
the per-sequence dataflow graph (repro.npec.ir) that lowering maps onto the
overlay.  Nothing here is symbolic-execution magic — the emitters mirror
the jnp modules in repro.models/*, which is exactly what makes the
functional executor (repro.npec.exec) checkable against those modules.

Every graph is ONE stack walk (`_stack`): `_embed`, then per layer one
block — post-norm for ``bert`` (paper Table 1), pre-norm for ``dense`` and
``moe`` — whose attention is ONE emitter (`_attend`) and whose FFN is
`_ffn`, then the final norm and `_logits_head`.  What differs between
graphs is the family (`_Dims`) and the phase (`_Rows`), which says where
the query rows' keys come from:
  * ``prompt`` — S rows, keys made in the graph, causal or bidirectional
    (`trace_model`; `trace_prefill`, which also exports each kv head's
    post-rope k/v under the decode banks' names);
  * ``slice``  — C prompt rows at a (C,) `pos_ids` input, appended into
    (T, head_dim) cache banks, each row masked to its own position
    (`trace_prefill(cache_len=T)`);
  * ``decode`` — ONE new token per slot at `pos` (a scalar, or a (B,)
    vector for B > 1 slots sharing one stream), appended into each slot's
    banks, which are rings under `window` (`trace_decode`), mirroring
    `models/transformer.decode_step` and the causal
    `models/bert.decode_step` serving variant op for op.

Families:
  * ``bert``   — post-norm encoder, incl. GQA smoke shapes; the dims-only
                 `*_shape` builders trace a `core.cycles.BertShape` through
                 the same walk (no biases, no layernorm beta).
  * ``dense``  — pre-norm decoder blocks (RoPE + GQA + gated/plain MLP).
  * ``moe``    — dense blocks whose FFN is a mixture-of-experts every
                 `interleave` layers (granite: all-MoE; llama4:
                 interleaved + shared expert): router logits as an MMU
                 matmul, router probabilities as NVU softmax/sigmoid,
                 top-k selection + capacity-bounded dispatch as
                 topk/gather/scatter_slot IR ops, per-expert FFN matmuls
                 gated by capacity C = max(1, int(S*k/E * cf)), and
                 the gate-weighted combine — mirroring `models/moe.apply`
                 (including softmax-gate renormalization and
                 overflow-drop semantics) op for op.  Prefill only.
Unsupported families/features raise `CompileError` naming the gap (one
gate, `_gate`); ROADMAP.md "Open items" tracks the remaining ones.

Heads are traced individually (per-head QK^T/softmax/AV, or per kv group
in decode), matching the overlay's execution granularity — the
schedule-level softmax/matmul overlap of paper §7.2.1 then *emerges* in
repro.npec.schedule from the dependency structure, with no hand-placed
pipelining in the emission order.  Tags (`blk3.h5.q`,
`enc0.kv1.s2.softmax`) are read by repro.npec.schedule and
repro.npec.fleet.partition, so their spelling is part of the output.

CLI smoke (used by scripts/ci.sh):
    PYTHONPATH=src python -m repro.npec.trace --model bert_base --check
"""
from __future__ import annotations

import argparse
from typing import NamedTuple, Optional

from repro.config import ModelConfig
from repro.npec.ir import Graph, GraphBuilder


class CompileError(NotImplementedError):
    """A model (or model feature) the compiler cannot lower yet."""


# ---------------------------------------------------------------------------
# The feature gate
# ---------------------------------------------------------------------------

_SERVING_FAMILIES = ("bert", "dense")      # families with decode streams


def _gate(cfg: ModelConfig, phase: str, *, window: bool = False) -> None:
    """Raise CompileError naming what npec cannot lower of `cfg` for
    `phase` ("model": `trace_model`; "prefill"/"decode": the two halves of
    serving).  window=True lifts the "sliding"-attention gap: a ring cache
    of capacity cfg.window IS sliding-window attention."""
    fam, name = cfg.family, cfg.name
    if phase == "model" and fam not in ("bert", "dense", "moe"):
        raise CompileError(
            f"npec has no tracer for family {fam!r} ({name!r}) "
            "yet (see ROADMAP.md Open items)")
    if phase != "model" and fam not in _SERVING_FAMILIES:
        gap = ("MoE decode streams (per-token capacity-1 dispatch)"
               if fam == "moe" else f"decode streams for family {fam!r}")
        why = ", so it cannot serve this family" if phase == "prefill" else ""
        raise CompileError(f"npec cannot lower {gap} yet ({name!r}){why} "
                           "(see ROADMAP.md Open items)")
    if fam == "bert":
        return
    if phase == "prefill" and not cfg.causal:
        raise CompileError(f"npec serving prefill needs a causal model; "
                           f"{name!r} is bidirectional")
    attn_gap = (cfg.attention != "full"
                and not (window and cfg.attention == "sliding"))
    for feat, msg in (
            (cfg.moe is not None and fam != "moe", "MoE routing"),
            (attn_gap, f"{cfg.attention!r} attention streams"),
            (cfg.parallel_block, "parallel attn+mlp blocks"),
            (cfg.qk_norm, "per-head qk-norm"),
            (cfg.logit_softcap > 0, "logit softcapping"),
            (cfg.ssm is not None, "SSM recurrences"),
            (cfg.rope not in ("standard", "none"),
             f"{cfg.rope!r} positional encoding"),
    ):
        if feat:
            raise CompileError(
                f"npec cannot lower {msg} yet for {name!r} "
                "(see ROADMAP.md Open items)")


# ---------------------------------------------------------------------------
# What a graph is traced from: the model's dims and the phase's rows
# ---------------------------------------------------------------------------

class _Dims(NamedTuple):
    """What the stack walk reads of a model: a ModelConfig (`_dims`) or a
    raw `core.cycles.BertShape` (`_shape_dims`, which has no embedding or
    head, so `cfg` is None)."""
    cfg: Optional[ModelConfig]
    post_norm: bool               # bert's block; pre-norm otherwise
    H: int
    A: int
    KV: int
    hd: int
    F: int
    qkv_bias: bool
    mlp_bias: bool
    norm: str                     # "layernorm" | anything else: rmsnorm
    norm_beta: bool
    eps: float
    gated: bool
    act: str
    theta: Optional[float]        # RoPE base, None without rope


def _dims(cfg: ModelConfig) -> _Dims:
    """bert blocks are layernorm(eps=1e-12, with beta) + plain GELU MLP
    whatever the config says; dense/moe follow `models/common.apply_norm`
    at its default eps=1e-6."""
    bert = cfg.family == "bert"
    return _Dims(
        cfg, bert, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
        cfg.head_dim, cfg.d_ff, cfg.qkv_bias, cfg.mlp_bias,
        "layernorm" if bert else cfg.norm, bert or cfg.norm_bias,
        1e-12 if bert else 1e-6, not bert and cfg.mlp_type == "gated",
        "gelu" if bert else cfg.activation,
        cfg.rope_theta if cfg.rope == "standard" and not bert else None)


def _shape_dims(shape, *, norm_beta: bool = False) -> _Dims:
    """A BertShape's encoder with no biases (bias adds are folded and cost
    nothing, so the instruction stream is cycle-identical either way);
    its block layernorms carry beta only in `trace_bert_shape`."""
    return _Dims(None, True, shape.hidden, shape.heads, shape.heads,
                 shape.head_dim, shape.d_ff, False, False, "layernorm",
                 norm_beta, 1e-12, False, "gelu", None)


class _Rows(NamedTuple):
    """The graph's query rows and where their keys come from (module
    docstring).  `pos` / `pos_slots` are filled in by `_stack`: the
    slice's `pos_ids` or decode's `pos` input node, and for B > 1 decode
    slots the hoisted per-slot scalar selects of `pos` that mask each
    slot's softmax."""
    phase: str                    # "prompt" | "slice" | "decode"
    n: int                        # query rows: S, C or B
    T: int = 0                    # cache-bank capacity (slice, decode)
    causal: bool = False          # prompt mask
    export_kv: bool = False       # prompt: export k/v for decode banks
    window: bool = False          # decode: ring banks
    pos: Optional[int] = None
    pos_slots: tuple = ()


# ---------------------------------------------------------------------------
# The one attention emitter
# ---------------------------------------------------------------------------

def _attend(b: GraphBuilder, x: int, l: int, d: _Dims, rows: _Rows,
            tag: str) -> int:
    """Attention of layer `l` over hidden state `x`; returns the output
    projection node.  It walks the kv groups and their query heads once.

    * prompt: per query head q, QK^T, softmax (causal or not) and AV; the
      group's k/v are projected right after its first head's q and
      tagged by that head (`h{i}.k`), and optionally exported as
      `{tag}.kv{j}.k/v`.  Heads are emitted in plain dataflow order —
      deferring the AV matmuls past the next head's projections is the
      *scheduler's* job, not the tracer's.
    * slice: the group's (C, hd) k/v (post-rope at `pos_ids`) burst-append
      into its bank (`cache_append` rows=C), then each query head runs a
      (C, T) QK^T over the *updated* bank with a row-masked softmax (row r
      attends to slots <= pos_ids[r]: same-slice future keys are in the
      bank but masked), which is what makes the chunked path bitwise-equal
      to the whole prompt in float mode.
    * decode: the new k/v append into each slot's bank at `pos`; the
      group's g skinny query rows stack into one (g, T) QK^T, pos-masked
      softmax and AV per slot — how GQA decode amortizes the cache read,
      in lockstep with the grouped einsum of models/common.attention_scores.
      B > 1 slots share every weight projection as one merged B-row MMU
      tile (rope rotates row s at pos[s]); each slot keeps its own bank
      (`{tag}.kv{j}.slot{s}.k/v`) and its own attention stream, selected
      by `slot_select`, since every slot attends to a different cache.
      Under `window` each bank is a ring: the append wraps at T and the
      pos-masked softmax saturates to the full ring once pos >= T, so the
      QK^T tile stays (g, T) with T the window, never the full context.
    """
    H, hd, g = d.H, d.hd, d.A // d.KV
    prompt, decode = rows.phase == "prompt", rows.phase == "decode"
    B = rows.n if decode else 1

    def rope(n, t):
        return (n if d.theta is None
                else b.rope(n, theta=d.theta, pos=rows.pos, tag=t))

    def q_proj(i):
        c = (i * hd, (i + 1) * hd)
        bq = (b.param(("blocks", "bq"), (hd,), layer=l, cols=c)
              if d.qkv_bias else None)
        q = b.matmul(x, b.param(("blocks", "wq"), (H, hd), layer=l, cols=c),
                     bias=bq, tag=f"{tag}.h{i}.q")
        return rope(q, f"{tag}.h{i}.q_rope")

    def kv_proj(j, t):
        c = (j * hd, (j + 1) * hd)
        bk, bv = ((b.param(("blocks", "bk"), (hd,), layer=l, cols=c),
                   b.param(("blocks", "bv"), (hd,), layer=l, cols=c))
                  if d.qkv_bias else (None, None))
        k = b.matmul(x, b.param(("blocks", "wk"), (H, hd), layer=l, cols=c),
                     bias=bk, tag=f"{t}.k")
        k = rope(k, f"{t}.k_rope")
        v = b.matmul(x, b.param(("blocks", "wv"), (H, hd), layer=l, cols=c),
                     bias=bv, tag=f"{t}.v")
        return k, v

    def scores(qs, k, v, valid, t):
        """QK^T/softmax/AV of one head, or of a stacked decode group."""
        qg = qs[0] if len(qs) == 1 else b.concat(qs, axis=-2,
                                                 tag=f"{t}.qstack")
        qk = b.matmul(qg, k, transpose_b=True, scale=hd ** -0.5,
                      tag=f"{t}.qk")
        sm = b.softmax(qk, causal=rows.causal, valid_upto=valid,
                       tag=f"{t}.softmax")
        av = b.matmul(sm, v, tag=f"{t}.av")
        return (av if len(qs) == 1
                else b.reshape(av, (1, len(qs) * hd), tag=f"{t}.flatten"))

    z = [[] for _ in range(B)]               # slot -> per-head/group rows
    for j in range(d.KV):
        heads = range(j * g, (j + 1) * g)
        if prompt:
            for i in heads:
                q = q_proj(i)
                if i == j * g:
                    k, v = kv_proj(j, f"{tag}.h{i}")
                    if rows.export_kv:
                        b.g.kv_exports[f"{tag}.kv{j}.k"] = k
                        b.g.kv_exports[f"{tag}.kv{j}.v"] = v
                z[0].append(scores([q], k, v, None, f"{tag}.h{i}"))
            continue
        k, v = kv_proj(j, f"{tag}.kv{j}")
        banks = []
        for s in range(B):
            name = f"{tag}.kv{j}" + (f".slot{s}" if B > 1 else "")
            kc = b.cache(f"{name}.k", (rows.T, hd))
            vc = b.cache(f"{name}.v", (rows.T, hd))
            slot = s if B > 1 else None
            banks.append((
                b.cache_append(kc, k, rows.pos, slot=slot,
                               window=rows.window),
                b.cache_append(vc, v, rows.pos, slot=slot,
                               window=rows.window)))
        if not decode:
            kc, vc = banks[0]
            for i in heads:
                z[0].append(scores([q_proj(i)], kc, vc, rows.pos,
                                   f"{tag}.h{i}"))
            continue
        qs = [q_proj(i) for i in heads]
        for s, (kc, vc) in enumerate(banks):
            if B == 1:
                z[0].append(scores(qs, kc, vc, rows.pos, f"{tag}.kv{j}"))
                continue
            t = f"{tag}.kv{j}.s{s}"
            rows_s = [b.slot_select(q, s, tag=f"{t}.q{gi}")
                      for gi, q in enumerate(qs)]
            z[s].append(scores(rows_s, kc, vc, rows.pos_slots[s], t))
    if not decode:
        zz = b.concat(z[0], tag=f"{tag}.merge_heads")
    else:
        per_slot = [p[0] if len(p) == 1 else b.concat(
            p, tag=f"{tag}{f'.s{s}' if B > 1 else ''}.merge_heads")
            for s, p in enumerate(z)]
        zz = (per_slot[0] if B == 1
              else b.concat(per_slot, axis=-2, tag=f"{tag}.merge_slots"))
    wo = b.param(("blocks", "wo"), (d.A * hd, H), layer=l)
    return b.matmul(zz, wo, tag=f"{tag}.attn.out")


# ---------------------------------------------------------------------------
# The one stack walk: embed -> blocks (attention + FFN) -> norm -> head
# ---------------------------------------------------------------------------

def _norm(b: GraphBuilder, d: _Dims, x: int, path, layer, tag: str) -> int:
    gamma = b.param(tuple(path) + ("gamma",), (d.H,), layer=layer)
    if d.norm == "layernorm":
        beta = (b.param(tuple(path) + ("beta",), (d.H,), layer=layer)
                if d.norm_beta else None)
        return b.layernorm(x, gamma, beta, eps=d.eps, tag=tag)
    return b.rmsnorm(x, gamma, eps=d.eps, tag=tag)


def _embed(b: GraphBuilder, d: _Dims, rows: _Rows) -> int:
    """Token embedding; bert adds its learned positions (the prompt's
    first S rows, or gathered at `pos` / `pos_ids`), the type embedding
    and the embedding layernorm."""
    cfg, H = d.cfg, d.H
    tokens = b.input("tokens", (rows.n,), dtype="int32")
    x = b.embed(tokens, b.param(("embed",), (cfg.vocab_size, H)),
                tag="embed.tok")
    if not d.post_norm:
        return x
    if rows.phase == "prompt":
        x = b.add(x, b.param(("pos_embed",), (rows.n, H), rows=(0, rows.n)),
                  tag="embed.pos")
    else:
        pe = b.embed(rows.pos, b.param(("pos_embed",),
                                       (cfg.max_position, H)),
                     tag="embed.pos")
        x = b.add(x, pe, tag="embed.pos_add")
    x = b.add(x, b.param(("type_embed",), (H,), index=0), tag="embed.type")
    return _norm(b, d, x, ("ln_embed",), None, "embed.ln")


def _mlp(b: GraphBuilder, d: _Dims, x: int, l: int, tag: str) -> int:
    """Gated (SwiGLU/GeGLU) or plain two-matmul MLP; returns the down
    projection (pre-residual)."""
    def w(name, shape):
        return b.param(("blocks", "mlp", name), shape, layer=l)
    H, F = d.H, d.F
    if d.gated:
        gt = b.act(b.matmul(x, w("wg", (H, F)), tag=f"{tag}.ffg"), d.act,
                   tag=f"{tag}.act")
        up = b.matmul(x, w("wu", (H, F)), tag=f"{tag}.ffu")
        return b.matmul(b.mul(gt, up, tag=f"{tag}.gate"), w("wd", (F, H)),
                        tag=f"{tag}.ffd")
    b1 = w("b1", (F,)) if d.mlp_bias else None
    mid = b.act(b.matmul(x, w("w1", (H, F)), bias=b1, tag=f"{tag}.ff1"),
                d.act, tag=f"{tag}.act")
    b2 = w("b2", (H,)) if d.mlp_bias else None
    return b.matmul(mid, w("w2", (F, H)), bias=b2, tag=f"{tag}.ff2")


def _ffn(b: GraphBuilder, d: _Dims, x: int, l: int, tag: str) -> int:
    """Layer l's FFN: MoE iff (l+1) % interleave == 0
    (`models/transformer.layer_is_moe`), the MLP otherwise.  The two kinds
    keep separate stacked params, indexed by how many layers of that kind
    came before l."""
    m = d.cfg.moe if d.cfg is not None else None
    if m is None:
        return _mlp(b, d, x, l, tag)
    if (l + 1) % m.interleave == 0:
        return _moe_ffn(b, d.cfg, x, l // m.interleave,
                        S=b.g.node(x).shape[0], tag=tag)[0]
    return _mlp(b, d, x, l - l // m.interleave, tag)


def _logits_head(b: GraphBuilder, cfg: ModelConfig, x: int) -> int:
    """Final vocab projection: tied configs reuse the (V, H) embedding
    table transposed (still MMU-resident), untied use lm_head (H, V)."""
    V, H = cfg.vocab_size, cfg.d_model
    if cfg.tie_embeddings or cfg.family == "bert":
        return b.matmul(x, b.param(("embed",), (V, H)), transpose_b=True,
                        tag="logits")
    return b.matmul(x, b.param(("lm_head",), (H, V)), tag="logits")


def _stack(d: _Dims, rows: _Rows, layers: Optional[int],
           include_embed: bool, *, head: bool) -> Graph:
    """The graph of `layers` blocks (all of the model's for None) over
    `rows`: post-norm bert blocks (X2 = LN(X + attn); X5 = LN(X2 +
    MLP(X2)), tagged `enc{l}`) or pre-norm blocks (X + attn(LN(X)), then
    X + FFN(LN(X)), tagged `blk{l}`, closed by the final norm `ln_f`).
    include_embed=False starts from an (n, H) hidden-state input `x`;
    head=True ends in the vocab logits."""
    if layers is None:
        layers = d.cfg.num_layers
    b = GraphBuilder()
    if rows.phase == "slice":
        rows = rows._replace(pos=b.input("pos_ids", (rows.n,),
                                         dtype="int32"))
    elif rows.phase == "decode" and rows.n == 1:
        rows = rows._replace(pos=b.input("pos", (), dtype="int32"))
    elif rows.phase == "decode":
        pos = b.input("pos", (rows.n,), dtype="int32")
        rows = rows._replace(pos=pos, pos_slots=tuple(
            b.slot_select(pos, s, tag=f"pos.s{s}") for s in range(rows.n)))
    x = (_embed(b, d, rows) if include_embed
         else b.input("x", (rows.n, d.H)))
    for l in range(layers):
        if d.post_norm:
            tag = f"enc{l}"
            x = _norm(b, d, b.add(x, _attend(b, x, l, d, rows, tag),
                                  tag=f"{tag}.res_a"),
                      ("blocks", "ln1"), l, f"{tag}.ln_a")
            x = _norm(b, d, b.add(x, _ffn(b, d, x, l, tag),
                                  tag=f"{tag}.res_b"),
                      ("blocks", "ln2"), l, f"{tag}.ln_b")
            continue
        tag = f"blk{l}"
        h = _norm(b, d, x, ("blocks", "ln1"), l, f"{tag}.ln1")
        x = b.add(x, _attend(b, h, l, d, rows, tag), tag=f"{tag}.res_a")
        h = _norm(b, d, x, ("blocks", "ln2"), l, f"{tag}.ln2")
        x = b.add(x, _ffn(b, d, h, l, tag), tag=f"{tag}.res_b")
    if not d.post_norm:
        x = _norm(b, d, x, ("ln_f",), None, "ln_f")
    if head:
        x = _logits_head(b, d.cfg, x)
    b.output(x)
    return b.g


# ---------------------------------------------------------------------------
# MoE FFN (granite: every layer; llama4: every `interleave`-th layer)
# ---------------------------------------------------------------------------

def moe_capacity(cfg: ModelConfig, seq: int) -> int:
    """Expert capacity C = max(1, int(S*k/E * capacity_factor)) — the
    per-sequence slot budget `models/moe.apply` dispatches into."""
    m = cfg.moe
    return max(1, int(seq * m.top_k / m.num_experts * m.capacity_factor))


def _moe_ffn(b: GraphBuilder, cfg: ModelConfig, x: int, mi: int, *, S: int,
             tag: str):
    """One MoE FFN block mirroring `models/moe.apply` op for op:
    router matmul (MMU) -> softmax/sigmoid probabilities (NVU) -> top-k
    gates + indices (renormalized for softmax routers with k > 1) ->
    capacity-bounded scatter into (E, C, D) slot buffers (MWU) -> E
    per-expert gated-MLP matmul streams over C-row tiles (skinny when
    C < 128 PE rows) -> gate-weighted combine gather (MRU) -> optional
    shared expert.  Router and expert matmuls are pinned to the float
    path (`quantize=False`): the reference computes them as plain
    activation-dtype einsums even in NPE mode; the shared expert routes
    through `cm.dense` and stays quantizable.

    Returns (out_node, aux) where aux exposes the routing nodes
    (gates/ids/dispatch/combine) for conformance and property tests.
    """
    m = cfg.moe
    H, F, E, k = cfg.d_model, cfg.d_ff, m.num_experts, m.top_k
    cap = moe_capacity(cfg, S)
    router = b.param(("blocks", "moe", "router"), (H, E), layer=mi)
    logits = b.matmul(x, router, quantize=False, tag=f"{tag}.router")
    if m.router_act == "sigmoid":
        probs = b.act(logits, "sigmoid", tag=f"{tag}.router_probs")
    else:
        probs = b.softmax(logits, tag=f"{tag}.router_probs")
    renorm = m.router_act == "softmax" and k > 1
    gates, ids = b.topk(probs, k, renorm=renorm, tag=f"{tag}.topk")
    buf = b.scatter_slot(x, ids, num_experts=E, capacity=cap, top_k=k,
                         tag=f"{tag}.dispatch")
    outs = []
    for e in range(E):
        etag = f"{tag}.x{e}"
        xe = b.gather(buf, index=e, tag=f"{etag}.gather")
        wg = b.param(("blocks", "moe", "wg"), (H, F), layer=mi, index=e)
        wu = b.param(("blocks", "moe", "wu"), (H, F), layer=mi, index=e)
        wd = b.param(("blocks", "moe", "wd"), (F, H), layer=mi, index=e)
        gt = b.act(b.matmul(xe, wg, quantize=False, tag=f"{etag}.ffg"),
                   cfg.activation, tag=f"{etag}.act")
        up = b.matmul(xe, wu, quantize=False, tag=f"{etag}.ffu")
        h = b.mul(gt, up, tag=f"{etag}.gate")
        outs.append(b.matmul(h, wd, quantize=False, tag=f"{etag}.ffd"))
    stacked = (outs[0] if E == 1
               else b.concat(outs, axis=-2, tag=f"{tag}.expert_stack"))
    out = b.gather(stacked, expert_ids=ids, gates=gates, num_experts=E,
                   capacity=cap, top_k=k, tag=f"{tag}.combine")
    aux = dict(gates=gates, ids=ids, dispatch=buf, combine=out)
    if m.shared_expert:
        sg = b.act(b.matmul(x, b.param(("blocks", "moe", "shared", "wg"),
                                       (H, F), layer=mi),
                            tag=f"{tag}.shared.ffg"),
                   cfg.activation, tag=f"{tag}.shared.act")
        su = b.matmul(x, b.param(("blocks", "moe", "shared", "wu"), (H, F),
                                 layer=mi), tag=f"{tag}.shared.ffu")
        sh = b.mul(sg, su, tag=f"{tag}.shared.gate")
        sd = b.matmul(sh, b.param(("blocks", "moe", "shared", "wd"), (F, H),
                                  layer=mi), tag=f"{tag}.shared.ffd")
        out = b.add(out, sd, tag=f"{tag}.shared.res")
    return out, aux


def trace_moe_block(cfg: ModelConfig, seq: int, *, layer: int = 0,
                    debug_outputs: bool = False) -> Graph:
    """Graph of ONE MoE FFN block over an (S, D) hidden-state input — the
    isolated unit the dispatch property tests validate bitwise against
    `models/moe.apply` (feed params under {"blocks": {"moe": ...}}).
    debug_outputs=True additionally marks the routing intermediates
    (gates, indices, dispatch buffer) as graph outputs."""
    b = GraphBuilder()
    x = b.input("x", (seq, cfg.d_model))
    out, aux = _moe_ffn(b, cfg, x, layer, S=seq, tag=f"moe{layer}")
    b.output(out)
    if debug_outputs:
        b.output(aux["gates"])
        b.output(aux["ids"])
        b.output(aux["dispatch"])
    return b.g


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def trace_model(cfg: ModelConfig, seq: int, *, layers: Optional[int] = None,
                include_embed: bool = True) -> Graph:
    """Emit the IR graph for `cfg` at sequence length `seq`.

    layers=N truncates the stack (cycle models usually compile one layer
    and scale); include_embed=False starts from a hidden-state input.
    bert traces the paper's bidirectional encoder, which ends at its last
    layernorm; dense and moe trace their causal stack and vocab head.
    """
    _gate(cfg, "model")
    bert = cfg.family == "bert"
    return _stack(_dims(cfg), _Rows("prompt", seq,
                                    causal=not bert and cfg.causal),
                  layers, include_embed,
                  head=include_embed and not bert)


def trace_bert_shape(shape, *, layers: int = 1) -> Graph:
    """Encoder-only graph from a raw `repro.core.cycles.BertShape` — the
    dims-only path `core.cycles` uses as its npec backend (no ModelConfig,
    no biases: bias adds are folded and cost nothing, so the instruction
    stream is cycle-identical either way)."""
    return _stack(_shape_dims(shape, norm_beta=True),
                  _Rows("prompt", shape.seq), layers, False, head=False)


def trace_decode(cfg: ModelConfig, cache_len: int, *,
                 layers: Optional[int] = None,
                 include_embed: bool = True, batch: int = 1,
                 window: bool = False) -> Graph:
    """Emit the one-new-token decode graph for `cfg` over a KV cache of
    capacity `cache_len`.

    The graph takes a scalar int32 `pos` input (the current cache length):
    the new k/v append at slot `pos`, softmax masks slots > pos, and RoPE
    rotates at `pos` — so ONE compiled stream serves every step t < T,
    exactly how the overlay would execute autoregressive serving (load the
    stream once, re-run it per token).  Executed statefully by
    repro.npec.exec.DecodeSession; step outputs match
    `models/transformer.decode_step` / `models/bert.decode_step`
    (tests/test_npec_decode.py).

    batch=B > 1 emits the *batched* decode stream (the serving engine's
    step, repro.npec.runtime): B slots share one stream, weight
    projections merge into B-row MMU tiles, `pos` becomes a (B,) vector,
    and each slot keeps its own cache bank — bitwise-equivalent to B
    independent per-sequence rollouts (tests/test_npec_runtime.py).

    window=True compiles the *ring* (sliding-window) variant: cache banks
    of capacity `cache_len` whose appends wrap at cache_len (cache_append
    attr window), so positions grow unbounded while the QK^T tile stays
    banded at `cache_len` keys.  For "sliding"-attention configs
    (starcoder2) `cache_len` must equal `cfg.window` — the ring then
    matches `models/transformer.decode_step`'s window caches exactly at
    EVERY position.  Full-attention configs may also trace windowed (a
    serving mode: the smallest bucket that never grows) — identical to
    the full model only while total tokens <= cache_len.
    """
    _gate(cfg, "decode", window=window)
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if window and cfg.attention == "sliding" and cache_len != cfg.window:
        raise CompileError(
            f"windowed decode for {cfg.name!r} needs cache_len == "
            f"cfg.window ({cfg.window}), got {cache_len} — any other ring "
            "capacity diverges from the model's sliding-window mask")
    return _stack(_dims(cfg), _Rows("decode", batch, T=cache_len,
                                    window=window),
                  layers, include_embed, head=include_embed)


def trace_prefill(cfg: ModelConfig, seq: int, *,
                  layers: Optional[int] = None,
                  include_embed: bool = True,
                  cache_len: Optional[int] = None,
                  window: bool = False) -> Graph:
    """Emit the *serving prefill* graph for a `seq`-token prompt: a causal
    prefill pass whose per-kv-head post-rope (S, hd) k/v tensors are
    registered in `Graph.kv_exports` under the decode streams' canonical
    cache names, so one executed prefill seeds a decode slot's cache banks
    (`DecodeSession.load_slot`) — numerically what rolling the prompt
    token-by-token through the decode stream computes, at full-width MMU
    tiles instead of S skinny 1-row steps.

    bert traces its *causal* serving variant with the logits head
    (mirroring an incremental `models/bert.decode_step` rollout over the
    prompt, NOT the bidirectional encoder); dense traces its ordinary
    causal prefill.  Families without decode streams raise `CompileError`
    (the serving engine needs both halves).

    cache_len=T switches to the *chunked* mode: the graph is one causal
    SLICE of `seq` prompt rows over the decode streams' (T, head_dim)
    cache banks — a (seq,) int32 `pos_ids` input carries each row's
    absolute prompt position, the new k/v rows `cache_append` into the
    banks at those positions, and a row-masked softmax over the updated
    cache gives row r the same valid key set the monolithic causal row
    has.  Executing ceil(S/chunk) such slices (carrying cache_updates
    between them, as `NPEEngine` does) seeds a cache bank bitwise-equal
    to one whole-prompt prefill in float mode.

    window=True serves a *windowed* engine (ring decode banks of capacity
    cfg.window): the prompt must fit the window — a causal prefill of
    S <= W tokens is EXACTLY what the sliding-window model computes (every
    query's window covers the whole prefix) — which also lifts the
    "sliding"-attention gate for those configs.
    """
    if window and cfg.attention == "sliding" and seq > cfg.window:
        raise CompileError(
            f"windowed prefill for {cfg.name!r} holds at most cfg.window "
            f"({cfg.window}) prompt tokens, got {seq} — longer prompts "
            "need banded prefill tiles (see ROADMAP.md Open items)")
    if cache_len is not None and seq > cache_len:
        raise ValueError(
            f"prefill slice of {seq} rows exceeds the cache capacity "
            f"{cache_len}")
    _gate(cfg, "prefill", window=window)
    rows = (_Rows("prompt", seq, causal=True, export_kv=True)
            if cache_len is None else _Rows("slice", seq, T=cache_len))
    return _stack(_dims(cfg), rows, layers, include_embed,
                  head=include_embed)


def trace_prefill_slice_shape(shape, cache_len: int, rows: int, *,
                              layers: int = 1) -> Graph:
    """Headless chunked-prefill slice graph from a raw `core.cycles`
    BertShape — the dims-only path `core.cycles.chunked_prefill_cycles`
    uses to cost the per-chunk stall bound (no ModelConfig, no biases, no
    embedding/logits head; per-layer streams are identical, so cycle
    totals scale linearly in layer count)."""
    return _stack(_shape_dims(shape), _Rows("slice", rows, T=cache_len),
                  layers, False, head=False)


def trace_decode_bert_shape(shape, cache_len: int, *, layers: int = 1,
                            batch: int = 1, window: bool = False) -> Graph:
    """Headless decode-step graph from a raw `core.cycles.BertShape` — the
    dims-only path `core.cycles` uses to cost autoregressive serving (no
    ModelConfig, no biases, no embedding/logit head; per-layer streams are
    identical, so cycle totals scale linearly in layer count).  batch=B
    emits the merged B-slot stream (core.cycles.batched_decode_step_cycles).
    """
    return _stack(_shape_dims(shape), _Rows("decode", batch, T=cache_len,
                                            window=window),
                  layers, False, head=False)


# ---------------------------------------------------------------------------
# CLI smoke: trace + compile + (for BERT) cross-check vs the hand-built
# program and the jnp model
# ---------------------------------------------------------------------------

def _check_bert(args) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import cycles as cy
    from repro.core.overlay import NPEHardware
    from repro.models import bert as bert_mod
    from repro.models import common as cm
    from repro.models import registry
    from repro.npec import compile_model, execute, greedy_schedule

    hw = NPEHardware(vrwidth=args.vrwidth)
    cfg = get_config(args.model)
    compiled = compile_model(cfg, args.seq, hw, bits=args.bits,
                             include_embed=False)
    stats = greedy_schedule(compiled)
    per_enc = stats["total_cycles"] / cfg.num_layers
    hand = cy.schedule(cy.build_encoder_program(
        hw, cy.BertShape(seq=args.seq, hidden=cfg.d_model,
                         heads=cfg.num_heads, d_ff=cfg.d_ff,
                         encoders=cfg.num_layers), args.bits))
    dev = abs(per_enc - hand["total_cycles"]) / hand["total_cycles"]
    print(f"compiled {len(compiled.instrs)} instrs "
          f"({compiled.counts_by_unit()}); "
          f"{per_enc:.0f} cycles/encoder vs hand-built "
          f"{hand['total_cycles']:.0f} ({100 * dev:.2f}% deviation)")
    assert dev < 0.01, "compiled schedule deviates >1% from hand-built"

    # functional: smoke-scale executor vs the jnp encoder
    import dataclasses
    scfg = dataclasses.replace(get_config(args.model, smoke=True),
                               dtype="float32")
    params = registry.init_params(scfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                scfg.vocab_size)
    sc = compile_model(scfg, 32, hw, bits=args.bits)
    got = execute(sc, params, {"tokens": tokens})[0]
    want = bert_mod.encode(scfg, cm.cast_tree(params, scfg.dtype), tokens)
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    print(f"functional executor vs jnp encoder: max|err| = {err:.2e}")
    assert err < 1e-2, "executor diverges from the jnp model"


def _check_moe(args) -> None:
    """Compiled MoE prefill stream vs the family's jnp forward at smoke
    scale (op-by-op reference, see _check_decode for the disable_jit
    rationale); gated at the conformance suite's float tolerance."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.overlay import NPEHardware
    from repro.models import registry
    from repro.npec import compile_model, execute

    hw = NPEHardware(vrwidth=args.vrwidth)
    scfg = dataclasses.replace(get_config(args.model, smoke=True),
                               dtype="float32")
    S = 16
    params = registry.init_params(scfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                                scfg.vocab_size)
    compiled = compile_model(scfg, S, hw, bits=args.bits)
    with jax.disable_jit():
        got = execute(compiled, params, {"tokens": tokens})[0]
        want = registry.apply(scfg, params, tokens, remat=False)
    err = float(np.max(np.abs(np.asarray(got)
                              - np.asarray(want, np.float32))))
    print(f"moe stream vs registry.apply ({scfg.moe.num_experts} experts, "
          f"top-{scfg.moe.top_k}): max|err| = {err:.2e}")
    assert err < 1e-6, "moe stream diverges from the jnp forward"


def _check_decode(args) -> None:
    """Compiled decode stream vs the family's decode_step, rolled out over
    a smoke-scale cache.  The reference runs op-by-op (disable_jit) — XLA
    fusion would otherwise introduce ulp-level FMA noise; op-for-op the
    stream is bitwise faithful (tests/test_npec_decode.py gates 1e-6)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.overlay import NPEHardware
    from repro.models import registry
    from repro.npec import compile_decode
    from repro.npec.exec import DecodeSession

    hw = NPEHardware(vrwidth=args.vrwidth)
    scfg = dataclasses.replace(get_config(args.model, smoke=True),
                               dtype="float32")
    B, T = 2, 8
    params = registry.init_params(scfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                scfg.vocab_size)
    sess = DecodeSession(compile_decode(scfg, T, hw, bits=args.bits),
                         params, batch=B)
    L, KV, hd = scfg.num_layers, scfg.num_kv_heads, scfg.head_dim
    cache = {"full": {"k": jnp.zeros((L, B, T, KV, hd), jnp.float32),
                      "v": jnp.zeros((L, B, T, KV, hd), jnp.float32)}}
    err = 0.0
    with jax.disable_jit():
        for t in range(T):
            ref, cache = registry.decode_step(scfg, params, cache,
                                              tokens[:, t:t + 1],
                                              jnp.int32(t))
            got = sess.step(tokens[:, t:t + 1])
            err = max(err, float(np.max(np.abs(
                np.asarray(got) - np.asarray(ref, np.float32)))))
    print(f"decode stream vs decode_step ({T} tokens): max|err| = {err:.2e}")
    assert err < 1e-6, "decode stream diverges from decode_step"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="bert_base")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--bits", type=int, default=16)
    ap.add_argument("--vrwidth", type=int, default=1024)
    ap.add_argument("--decode", type=int, default=0, metavar="T",
                    help="compile a one-token decode step over a KV cache "
                         "of capacity T instead of a prefill stream")
    ap.add_argument("--check", action="store_true",
                    help="cross-check vs the hand-built program + jnp model "
                         "(and the decode_step rollout)")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.core.overlay import NPEHardware
    from repro.npec import (compile_decode, compile_model, greedy_schedule,
                            stream_schedule)

    cfg = get_config(args.model)
    hw = NPEHardware(vrwidth=args.vrwidth)
    if args.decode:
        compiled = compile_decode(cfg, args.decode, hw, bits=args.bits,
                                  include_embed=False)
    else:
        compiled = compile_model(cfg, args.seq, hw, bits=args.bits,
                                 include_embed=False)
    stats = greedy_schedule(compiled)
    tile = stream_schedule(compiled)
    print(f"{args.model}: {compiled.graph!r}")
    print(f"lowered to {len(compiled.instrs)} instrs "
          f"{compiled.counts_by_unit()}; scheduled "
          f"{stats['total_cycles']:.0f} cycles whole-op / "
          f"{tile['total_cycles']:.0f} tile-streaming "
          f"(MMU util {100 * tile['mmu_util']:.1f}%)")
    if args.decode:
        t = compiled.mmu_tiling_summary()
        print(f"skinny matmuls: {t['skinny_matmuls']} "
              f"(MMU row occupancy {100 * t['efficiency']:.2f}%)")
    if args.check:
        if cfg.family == "bert" and not args.decode:
            _check_bert(args)
        if cfg.family == "moe" and not args.decode:
            _check_moe(args)
        if cfg.family in _SERVING_FAMILIES:
            _check_decode(args)
        print("npec check OK")


if __name__ == "__main__":
    main()
