"""Functional executor: run a compiled program numerically.

Interprets the npec graph behind a `CompiledProgram` against the same
engines the jnp model zoo uses — `repro.core.nvu` for every nonlinearity
(float or PWL mode) and `repro.core.quant` for MMU-resident weight
matmuls — so a compiled instruction stream can be validated end-to-end
against the corresponding jnp model's outputs (tests/test_npec.py, and
`python -m repro.npec.trace --check`).

Semantics mirror the jnp modules op-for-op:
  * weight matmuls   -> `quant.dense_maybe_quant` (int8/int16 MMU when
                        npe_quant, one activation scale per row, so every
                        stream kind quantizes a token alike) + bias
                        epilogue;
  * QK^T / AV        -> f32-accumulated einsums on the activation path
                        (never quantized, matching `common.attention_scores`);
  * softmax / norms / activations -> `nvu.softmax` / layernorm / rmsnorm /
                        `nvu.activation` in float or PWL mode;
  * MoE routing       -> `jax.lax.top_k` + the GShard one-hot-cumsum
                        capacity dispatch / gate-weighted combine,
                        replicating `models/moe.apply` line for line
                        (router/expert matmuls are float-pinned via the
                        matmul `quantize=False` attr, exactly as the
                        reference computes them).

Each graph runs as ONE jitted XLA program (`executable`), memoized on
the graph per numerics: the node loop below runs only while JAX traces
it, and every later call is a single dispatch.  The graph and the
numerics are fixed in the program; the weights and the feeds (tokens,
`pos`, cache banks) are its arguments, so no weight becomes a constant
and a new position never retraces.  A node with ops of its own runs
through `_shared_node`, which JAX traces once per distinct node and
input shapes and reuses for every repeat (a layer's heads, a stack's
layers), so tracing costs the distinct nodes.  Buffers live in a
node-indexed environment and are freed at last use — the executor
reports the resulting peak live footprint, the quantity the overlay's
MMEM has to cover (paper §5.2).  That bookkeeping reads only shapes and
dtypes, so it runs at trace time and each call returns what its feed
shapes gave.

Each call and each `DecodeSession` bank update opens a wall-clock span
(repro.npec.obs.spans); each trace opens `npec.exec.trace`.  While a
profiler records, each call also marks its nodes, after the dispatch:
one empty span per node named by its op class.  The node's ops carry
the same class as a `jax.named_scope` in the compiled program.

Decode streams execute *statefully* through `DecodeSession`: the KV caches
(`cache` nodes) feed in as persistent MMEM-resident buffers, each step's
`cache_append` results are collected from `ExecResult.cache_updates` and
carried into the next step, and the scalar `pos` input advances — so one
compiled stream, executed t times, reproduces
`models/transformer.decode_step` / `models/bert.decode_step` rollouts
(tests/test_npec_decode.py: float 1e-6, NPE mode 5e-3).

Graphs are traced per-sequence; feeds may carry a leading batch axis and
every op vectorizes over it unchanged.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import nvu
from repro.core.quant import dense_maybe_quant
from repro.models import common as cm
from repro.npec.ir import FOLDED_OPS, Graph, Node
from repro.npec.lower import CompiledProgram
from repro.npec.obs.spans import (EXEC_EXECUTE, EXEC_PREFIX,
                                  EXEC_QUANTIZE_WEIGHT, EXEC_TRACE,
                                  SESSION_LOAD_SLOT, SESSION_MIGRATE,
                                  SESSION_RESET_SLOT, node_class, span)


@dataclass
class ExecResult:
    outputs: List[jnp.ndarray]
    peak_live_bytes: int
    n_instrs: int
    # name -> post-step cache value (decode graphs only); DecodeSession
    # persists these into the next step's feeds
    cache_updates: Dict[str, jnp.ndarray] = None
    # canonical cache name -> (S, head_dim) k/v rows (serving-prefill
    # graphs only, `trace_prefill`); DecodeSession.load_slot seeds a
    # decode slot's cache banks from these
    kv_exports: Dict[str, jnp.ndarray] = None

    def __getitem__(self, i: int) -> jnp.ndarray:
        return self.outputs[i]


def _param_leaf(params, node: Node):
    v = params
    for key in node.attrs["path"]:
        v = v[key]
    return v


def _slice_param(v, node: Node) -> jnp.ndarray:
    if node.attrs.get("layer") is not None:
        v = v[node.attrs["layer"]]
    if node.attrs.get("index") is not None:
        v = v[node.attrs["index"]]
    if node.attrs.get("rows") is not None:
        r0, r1 = node.attrs["rows"]
        v = v[r0:r1]
    if node.attrs.get("cols") is not None:
        c0, c1 = node.attrs["cols"]
        v = v[..., c0:c1]
    return jnp.asarray(v, jnp.float32)


def _matmul(node: Node, a, b, bias, *, weight_resident: bool,
            npe_quant: bool, bits: int):
    if weight_resident and not node.attrs.get("quantize", True):
        # float-pinned weight matmul (MoE router / expert streams):
        # `models/moe.apply` computes these as plain activation-dtype
        # einsums even in NPE mode, so the stream must too
        weight_resident = False
    if weight_resident:
        # MMU-resident weight (quantizable); a transposed resident weight
        # (the tied-embedding logits head) is stored transposed, exactly as
        # models/common.logits_out feeds embed.T to the quantized dense
        w = jnp.swapaxes(b, -1, -2) if node.attrs.get("transpose_b") else b
        y = dense_maybe_quant(a, w, None, npe_quant=npe_quant, bits=bits)
    elif node.attrs.get("transpose_b"):
        y = jnp.einsum("...ik,...jk->...ij", a, b,
                       preferred_element_type=jnp.float32)
    else:
        y = jnp.einsum("...ik,...kj->...ij", a, b,
                       preferred_element_type=jnp.float32)
    if node.attrs.get("scale") is not None:
        y = y * node.attrs["scale"]
    if bias is not None:
        y = y + bias
    return y


def _softmax(node: Node, x, *, pos=None, use_pwl: bool, segments: int):
    where = None
    if node.attrs.get("row_masked"):
        # chunked-prefill slice: pos is the (C,) absolute-position vector;
        # row r attends to cache slots <= pos[r] (the causal slice mask)
        sk = x.shape[-1]
        where = jnp.broadcast_to(jnp.arange(sk) <= pos[..., :, None],
                                 x.shape)
    elif node.attrs.get("cache_masked"):
        sk = x.shape[-1]
        where = jnp.broadcast_to(jnp.arange(sk) <= pos, x.shape)
    elif node.attrs.get("causal"):
        sq, sk = x.shape[-2], x.shape[-1]
        where = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        where = jnp.broadcast_to(where, x.shape)
    return nvu.softmax(x, axis=-1, use_pwl=use_pwl, segments=segments,
                       where=where)


def _layernorm(node: Node, x, gamma, beta, *, use_pwl: bool, segments: int):
    eps = node.attrs.get("eps", 1e-5)
    if use_pwl:
        return nvu.nvu_layernorm(x, gamma, beta, eps=eps, segments=segments)
    return cm.layernorm_exact(x, gamma, beta, eps)


def _rmsnorm(node: Node, x, gamma, *, use_pwl: bool, segments: int):
    eps = node.attrs.get("eps", 1e-6)
    if use_pwl:
        return nvu.nvu_rmsnorm(x, gamma, eps=eps, segments=segments)
    return cm.rmsnorm_exact(x, gamma, eps)


def _rope(node: Node, x, pos=None):
    """pos=None rotates row i at position i (prefill); a scalar `pos`
    rotates every row there (decode: the one new token); a (B,) vector
    rotates row s at pos[s] (batched decode: one merged projection, one
    new token per slot)."""
    s = x.shape[-2]
    lead = x.shape[:-2]
    b = 1
    for d in lead:
        b *= d
    x4 = x.reshape(b, s, 1, x.shape[-1])
    if pos is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    elif jnp.ndim(pos) == 1:
        positions = jnp.broadcast_to(pos.astype(jnp.int32), (b, s))
    else:
        positions = jnp.full((b, s), pos, jnp.int32)
    y = cm.apply_rope(x4, positions, node.attrs["theta"])
    return y.reshape(*lead, s, x.shape[-1])


def _topk(node: Node, x):
    """jax.lax.top_k over the last axis, exactly as `models/moe.apply`;
    the values node optionally renormalizes the selected gates (softmax
    routers with k > 1, via the shared `moe.renormalize_gates`)."""
    from repro.models import moe as moe_mod

    vals, ids = jax.lax.top_k(x, node.attrs["k"])
    if node.attrs["out"] == "indices":
        return ids.astype(jnp.int32)
    if node.attrs.get("renorm"):
        vals = moe_mod.renormalize_gates(vals)
    return vals


def _dispatch_mask(ids_flat, num_experts: int, capacity: int):
    """The GShard dispatch tensor (b, t, E, C) — the SAME
    `models/moe.dispatch_mask` the reference calls, so compiled streams'
    capacity-drop decisions are bitwise identical by construction."""
    from repro.models import moe as moe_mod

    return moe_mod.dispatch_mask(ids_flat, num_experts, capacity)


def _dispatch_mask_cached(memo, key, ids_flat, num_experts, capacity):
    """The dispatch mask is needed twice per MoE layer (scatter + combine)
    from the SAME indices node — memoize it per trace, keyed by the ids
    node id."""
    if memo is None:
        return _dispatch_mask(ids_flat, num_experts, capacity)
    k = (key, num_experts, capacity)
    if k not in memo:
        memo[k] = _dispatch_mask(ids_flat, num_experts, capacity)
    return memo[k]


def _scatter_slot(node: Node, x, ids, *, memo=None, key=None):
    """Capacity-bounded dispatch: (.., S, D) tokens -> (.., E, C, D) slot
    buffers (token-slots past capacity drop to zero rows)."""
    e = node.attrs["num_experts"]
    cap = node.attrs["capacity"]
    k = node.attrs["top_k"]
    lead = x.shape[:-2]
    s, d = x.shape[-2:]
    xf = x.reshape((-1, s, d))
    dispatch = _dispatch_mask_cached(memo, key, ids.reshape((-1, s * k)),
                                     e, cap)
    x_rep = jnp.repeat(xf, k, axis=1) if k > 1 else xf
    buf = jnp.einsum("btec,btd->becd", dispatch, x_rep)
    return buf.reshape(lead + (e, cap, d))


def _gather_combine(node: Node, stacked, ids, gates, *, memo=None,
                    key=None):
    """Weighted combine of the (.., E*C, D) stacked expert outputs back to
    (.., S, D) token order; dropped slots contribute zero and gates are
    NOT renormalized after the drop — `models/moe.apply` semantics."""
    e = node.attrs["num_experts"]
    cap = node.attrs["capacity"]
    k = node.attrs["top_k"]
    lead = stacked.shape[:-2]
    d = stacked.shape[-1]
    s = node.shape[-2]
    t = s * k
    out_buf = stacked.reshape((-1, e, cap, d))
    dispatch = _dispatch_mask_cached(memo, key, ids.reshape((-1, t)),
                                     e, cap)
    gated = dispatch * gates.reshape((-1, t))[..., None, None]
    out = jnp.einsum("btec,becd->btd", gated, out_buf)
    if k > 1:
        out = out.reshape(-1, s, k, d).sum(axis=2)
    return out.reshape(lead + (s, d))


def _cache_append(node: Node, c, new, posv):
    slot = node.attrs.get("slot")
    if slot is not None:
        # batched stream: row `slot` of the merged (B, hd) projection,
        # written at this slot's own position
        new = new[..., slot:slot + 1, :]
        posv = posv[..., slot]
    cap = node.shape[-2]
    if node.attrs.get("rows"):
        # chunked-prefill burst: write all C rows of `new` at their
        # absolute positions posv[r].  The one-hot einsum copies each row
        # exactly (1.0 * x plus zeros), so a chunked bank is bitwise-equal
        # to the monolithic prefill's rows.
        idx = posv.astype(jnp.int32)
        onehot = (jnp.arange(cap, dtype=jnp.int32)[:, None]
                  == idx[None, :])
        write = jnp.einsum("cr,...rd->...cd", onehot.astype(new.dtype), new)
        keep = ~onehot.any(axis=1)
        return jnp.where(keep[:, None], c, write)
    if node.attrs.get("window"):
        # ring bank: the write wraps — the bank holds the last `cap`
        # tokens while the position counter keeps growing
        posv = posv % cap
    hit = (jnp.arange(cap, dtype=jnp.int32) == posv)[:, None]
    return jnp.where(hit, new, c)


class _NodeKey(NamedTuple):
    """Everything a node's value depends on besides its inputs' values."""
    op: str
    cls: str
    shape: Tuple[int, ...]
    attrs: Tuple[Tuple[str, Any], ...]   # sorted; a bank's name left out
    weight_resident: bool
    npe_quant: bool
    bits: int
    use_pwl: bool
    segments: int


def _node_value(key: _NodeKey, vals) -> jnp.ndarray:
    node = Node(-1, key.op, (), key.shape, attrs=dict(key.attrs))
    nvu_kw = dict(use_pwl=key.use_pwl, segments=key.segments)
    op = key.op
    if op == "matmul":
        a, b, *bias = vals
        return _matmul(node, a, b, bias[0] if bias else None,
                       weight_resident=key.weight_resident,
                       npe_quant=key.npe_quant, bits=key.bits)
    if op == "softmax":
        return _softmax(node, vals[0], pos=vals[1] if len(vals) > 1 else None,
                        **nvu_kw)
    if op == "layernorm":
        x, gamma, *beta = vals
        return _layernorm(node, x, gamma, beta[0] if beta else None,
                          **nvu_kw)
    if op == "rmsnorm":
        return _rmsnorm(node, *vals, **nvu_kw)
    if op == "act":
        return nvu.activation(node.attrs["fn"], key.use_pwl,
                              key.segments)(vals[0])
    if op == "rope":
        return _rope(node, *vals)
    if op == "cache_append":
        return _cache_append(node, *vals)
    raise NotImplementedError(f"executor has no rule for {op!r}")


@functools.partial(jax.jit, static_argnums=0)
def _shared_node(key: _NodeKey, *vals) -> jnp.ndarray:
    """A node's ops as a program of their own.  JAX traces it once per
    key and input shapes and reuses that trace, and its lowering, for
    every node that repeats it (a stack's layers, a layer's heads); XLA
    inlines the calls, so the compiled graph program is unchanged."""
    with jax.named_scope(key.cls):
        return _node_value(key, vals)


# ops whose nodes repeat a trace of `_shared_node`; the rest are a
# primitive or two, a weight's own slice, or (MoE routing) share a
# dispatch mask between nodes
_SHARED_OPS = frozenset(("matmul", "softmax", "layernorm", "rmsnorm", "act",
                         "rope", "cache_append"))


def _nbytes(x) -> int:
    return int(x.size) * x.dtype.itemsize


def _use_counts(graph: Graph) -> Dict[int, int]:
    """Reads of each node's value; a buffer is freed at its last read."""
    uses = {n.id: 0 for n in graph.nodes}
    for n in graph.nodes:
        for i in n.inputs:
            uses[i] += 1
    for o in graph.outputs:
        uses[o] += 1                            # outputs never freed
    for nid in graph.cache_updates.values():
        uses[nid] += 1                          # carried into the next step
    for nid in graph.kv_exports.values():
        uses[nid] += 1                          # handed to load_slot
    return uses


def _interpret(graph: Graph, params: Any, feeds: Dict[str, Any], *,
               npe_quant: bool, bits: int, use_pwl: bool, segments: int):
    """Run `graph`'s nodes in order on `feeds`; returns (outputs,
    cache_updates, kv_exports, peak live bytes).  Called while JAX
    traces a graph's program, so the arrays are tracers and the live-byte
    bookkeeping reads only their shapes and dtypes."""
    env: Dict[int, jnp.ndarray] = {}
    live = 0
    peak = 0
    mask_memo: Dict[Any, jnp.ndarray] = {}   # per-trace dispatch-mask cache
    uses = _use_counts(graph)

    def put(nid: int, val):
        nonlocal live, peak
        env[nid] = val
        live += _nbytes(val)
        peak = max(peak, live)

    def get(nid: int):
        nonlocal live
        val = env[nid]
        uses[nid] -= 1
        if uses[nid] == 0:
            live -= _nbytes(val)
            del env[nid]
        return val

    def run(node: Node, cls: str) -> None:
        op = node.op
        if op in _SHARED_OPS:
            vals = [get(i) for i in node.inputs]
            wres = (op == "matmul"
                    and graph.node(node.inputs[1]).op == "param")
            key = _NodeKey(op, cls, node.shape,
                           tuple(sorted((k, v) for k, v in node.attrs.items()
                                        if k != "name")),
                           wres, npe_quant, bits, use_pwl, segments)
            put(node.id, _shared_node(key, *vals))
        elif op == "param":
            put(node.id, _slice_param(_param_leaf(params, node), node))
        elif op == "input":
            x = jnp.asarray(feeds[node.attrs["name"]])
            put(node.id, x if node.dtype == "int32"
                else x.astype(jnp.float32))
        elif op == "add":
            put(node.id, get(node.inputs[0]) + get(node.inputs[1]))
        elif op == "mul":
            put(node.id, get(node.inputs[0]) * get(node.inputs[1]))
        elif op == "concat":
            put(node.id, jnp.concatenate([get(i) for i in node.inputs],
                                         axis=node.attrs["axis"]))
        elif op == "reshape":
            x = get(node.inputs[0])
            src = graph.node(node.inputs[0]).shape
            lead = x.shape[:x.ndim - len(src)]   # preserved batch axes
            put(node.id, x.reshape(lead + node.shape))
        elif op == "embed":
            tokens, table = get(node.inputs[0]), get(node.inputs[1])
            put(node.id, jnp.take(table, tokens, axis=0))
        elif op == "cache":
            put(node.id, jnp.asarray(feeds[node.attrs["name"]],
                                     jnp.float32))
        elif op == "topk":
            x = get(node.inputs[0])
            if len(node.inputs) > 1:
                get(node.inputs[1])     # indices ride the values pass
            put(node.id, _topk(node, x))
        elif op == "scatter_slot":
            put(node.id, _scatter_slot(node, get(node.inputs[0]),
                                       get(node.inputs[1]),
                                       memo=mask_memo,
                                       key=node.inputs[1]))
        elif op == "gather":
            if node.attrs["mode"] == "expert":
                buf = get(node.inputs[0])
                put(node.id, buf[..., node.attrs["index"], :, :])
            else:
                put(node.id, _gather_combine(node, get(node.inputs[0]),
                                             get(node.inputs[1]),
                                             get(node.inputs[2]),
                                             memo=mask_memo,
                                             key=node.inputs[1]))
        elif op == "slot_select":
            x = get(node.inputs[0])
            i = node.attrs["index"]
            if len(graph.node(node.inputs[0]).shape) == 1:
                put(node.id, x[..., i])
            else:
                put(node.id, x[..., i:i + 1, :])
        else:
            raise NotImplementedError(f"executor has no rule for {op!r}")

    for node in graph.nodes:
        cls = node_class(graph, node)
        with jax.named_scope(cls):
            run(node, cls)

    return ([env[o] for o in graph.outputs],
            {name: env[nid] for name, nid in graph.cache_updates.items()},
            {name: env[nid] for name, nid in graph.kv_exports.items()},
            peak)


def _signature(feeds: Dict[str, Any]) -> tuple:
    """Names, shapes and dtypes of `feeds`, the same for the arrays of a
    call and the tracers JAX traces them as."""
    return tuple((name, tuple(v.shape),
                  jax.dtypes.canonicalize_dtype(v.dtype).name)
                 for name, v in sorted(feeds.items()))


@dataclass
class _Executable:
    """One graph's jitted program under fixed numerics.  `peaks` holds
    the peak live bytes each traced feed signature gave; `marks` the
    node marks of one execution (`_mark`)."""
    fn: Callable
    feed_names: Tuple[str, ...]
    peaks: Dict[tuple, int]
    marks: Tuple[Tuple[str, bool], ...]


def _marks(graph: Graph, npe_quant: bool) -> Tuple[Tuple[str, bool], ...]:
    """(span name, quantizes its weight) of each node, in graph order."""
    out = []
    for node in graph.nodes:
        cls = node_class(graph, node)
        out.append((EXEC_PREFIX + cls, cls == "mmu" and npe_quant
                    and node.attrs.get("quantize", True)))
    return tuple(out)


def _mark(marks: Tuple[Tuple[str, bool], ...]) -> None:
    """One empty span per node of an execution, for a profile to count
    by class; a weight's quantization is marked inside its matmul."""
    for name, quantizes in marks:
        with span(name):
            if quantizes:
                with span(EXEC_QUANTIZE_WEIGHT):
                    pass


def executable(graph: Graph, *, npe_quant: bool, bits: int,
               use_pwl: bool, segments: int) -> _Executable:
    """`graph`'s jitted program for these numerics, made on first use
    and memoized on the graph: every engine that shares a compiled
    program shares its executable.  `fn(params, feeds)` returns
    (outputs, cache_updates, kv_exports); weights and feeds are
    arguments, the graph and the numerics are fixed."""
    key = (npe_quant, bits, use_pwl, segments)
    exe = graph.executables.get(key)
    if exe is None:
        peaks: Dict[tuple, int] = {}

        def program(params, feeds):
            with span(EXEC_TRACE, nodes=len(graph.nodes)):
                *res, peak = _interpret(graph, params, feeds,
                                        npe_quant=npe_quant, bits=bits,
                                        use_pwl=use_pwl, segments=segments)
            peaks[_signature(feeds)] = peak
            return tuple(res)

        names = tuple(dict.fromkeys(n.attrs["name"] for n in graph.nodes
                                    if n.op in ("input", "cache")))
        exe = graph.executables[key] = _Executable(
            jax.jit(program), names, peaks, _marks(graph, npe_quant))
    return exe


def _as_arg(v):
    return v if isinstance(v, jax.Array) else np.asarray(v)


def execute(program: Union[CompiledProgram, Graph], params: Any,
            feeds: Dict[str, Any], *, cfg: Optional[ModelConfig] = None,
            npe_quant: bool = False, bits: int = 8, use_pwl: bool = False,
            segments: int = 16) -> ExecResult:
    """Run the program on `feeds` (dict input-name -> array, optionally
    batched) with `params` (the registry parameter tree), as the graph's
    one jitted program (`executable`).  NPE numerics follow `cfg` when
    given (npe_quant / npe_quant_bits / npe_pwl / npe_pwl_segments),
    else the explicit keyword flags."""
    graph = program.graph if isinstance(program, CompiledProgram) else program
    n_instrs = (len(program.instrs) if isinstance(program, CompiledProgram)
                else sum(n.op not in FOLDED_OPS for n in graph.nodes))
    if cfg is not None:
        npe_quant, bits = cfg.npe_quant, cfg.npe_quant_bits
        use_pwl, segments = cfg.npe_pwl, cfg.npe_pwl_segments
    exe = executable(graph, npe_quant=npe_quant, bits=bits, use_pwl=use_pwl,
                     segments=segments)
    args = {name: _as_arg(feeds[name]) for name in exe.feed_names}
    with span(EXEC_EXECUTE, nodes=len(graph.nodes)):
        outputs, updates, exports = exe.fn(params, args)
        if span.is_enabled():       # only a recording profiler keeps them
            _mark(exe.marks)
    return ExecResult(outputs, exe.peaks[_signature(args)], n_instrs,
                      updates, exports)


def _slot_banks(banks: Tuple[jnp.ndarray, ...],
                rows: Tuple[Any, ...]) -> Tuple[jnp.ndarray, ...]:
    """A slot's banks after a load or a reset: bank i holds `rows[i]`
    (cast to float32, lead axes dropped) at rows [0, S) and zeros after
    them; the banks past `rows` hold zeros only."""
    with span(EXEC_TRACE, rows=rows[0].shape[-2] if rows else 0,
              banks=len(banks)):
        out = []
        for i, bank in enumerate(banks):
            new = jnp.zeros_like(bank)
            if i < len(rows):
                r = rows[i].astype(jnp.float32)
                new = new.at[: r.shape[-2]].set(r.reshape(r.shape[-2:]))
            out.append(new)
    return tuple(out)


# One dispatch writes a whole slot.  The banks are donated and kept as
# arguments although only their shapes are read: unused, jit would drop
# them and each write would allocate the slot anew.
_write_slot = jax.jit(_slot_banks, donate_argnums=0, keep_unused=True)


class DecodeSession:
    """Stateful execution of a compiled decode stream.

    The software analogue of the overlay serving autoregressively: the
    instruction stream is compiled ONCE at cache capacity T, the KV caches
    live across steps (MMEM-resident state), and each `step()` runs the
    stream at the current `pos` — appending the new k/v, masking softmax to
    the valid prefix, and advancing the counter.

    Two stream shapes (distinguished by the graph's `pos` input):

      * **per-sequence** (scalar `pos`, `trace_decode(batch=1)`): one
        position counter; feeds may carry a leading batch axis and the
        whole graph vectorizes over it (`batch=` sizes the caches).
      * **batched-slot** ((B,) `pos`, `trace_decode(batch=B)`): B serving
        slots live *inside* the stream — per-slot cache banks
        (`...slotS.k/v`), a per-slot position vector, merged B-row weight
        projections.  Slots advance independently: `step(tokens, active=)`
        bumps only active slots, `reset_slot` recycles one for a new
        request, and `load_slot` seeds its banks from an executed prefill
        (`trace_prefill` kv exports).  Each writes all of the slot's banks
        with one dispatch of a jitted program that donates them, so a
        write reuses the slot's buffers.  This is the stream the serving
        engine (repro.npec.runtime) clocks.

    `params` is the registry parameter tree; NPE numerics follow `cfg`
    when given, else the explicit keyword flags (as in `execute`).
    """

    def __init__(self, compiled: CompiledProgram, params: Any, *,
                 batch: int = 1, cfg: Optional[ModelConfig] = None,
                 npe_quant: bool = False, bits: int = 8,
                 use_pwl: bool = False, segments: int = 16):
        graph = compiled.graph
        if not graph.caches:
            raise ValueError("not a decode graph: no cache nodes "
                             "(trace with repro.npec.trace.trace_decode)")
        self.compiled = compiled
        self.params = params
        self.cfg = cfg
        self.kw = dict(npe_quant=npe_quant, bits=bits, use_pwl=use_pwl,
                       segments=segments)
        pos_shape = graph.node(graph.inputs["pos"]).shape
        self.slots = pos_shape[0] if pos_shape else 1
        self.batched = bool(pos_shape)
        if self.batched and batch != 1:
            raise ValueError(
                "batched-slot streams carry their slots in-graph; "
                "feed-level vectorization (batch != 1) does not apply")
        lead = () if self.batched else (batch,)
        self.caches: Dict[str, jnp.ndarray] = {
            name: jnp.zeros(lead + graph.node(nid).shape, jnp.float32)
            for name, nid in graph.caches.items()}
        self.capacity = min(graph.node(nid).shape[-2]
                            for nid in graph.caches.values())
        # ring (sliding-window) streams: cache_append wraps at capacity,
        # the pos-masked softmax saturates, and positions grow unbounded —
        # the capacity-exhausted guard does not apply
        self.windowed = any(n.op == "cache_append"
                            and n.attrs.get("window")
                            for n in graph.nodes)
        self.pos = np.zeros(self.slots, np.int64) if self.batched else 0
        self._feed_name = next(n for n in graph.inputs if n != "pos")

    # --- per-sequence and batched stepping --------------------------------

    def step(self, tokens, active=None) -> jnp.ndarray:
        """Run one decode step.

        Per-sequence streams: `tokens` is (B, 1) int32 for full graphs
        (with embedding/logits head), or (B, 1, H) hidden states for
        headless graphs; returns (B, 1, V) logits (resp. hidden states)
        and advances the shared position.

        Batched-slot streams: `tokens` is (B,) (or (B, 1)) int32 — one
        token per slot — or (B, H) hidden states for headless graphs;
        `active` optionally masks which slots advance their position
        (idle slots still flow through the fixed stream, their outputs
        are ignored and their counters hold).  Returns the (B, V) step
        output.  Either mode raises on a pos overflow past the compiled
        cache capacity instead of silently masking to garbage.
        """
        if not self.batched:
            if self.pos >= self.capacity and not self.windowed:
                raise ValueError(
                    f"KV cache capacity {self.capacity} exhausted at "
                    f"pos={self.pos}; compile a longer stream")
            feeds: Dict[str, Any] = dict(self.caches)
            feeds["pos"] = jnp.int32(self.pos)
            feeds[self._feed_name] = tokens
            res = execute(self.compiled, self.params, feeds, cfg=self.cfg,
                          **self.kw)
            self.caches.update(res.cache_updates)
            self.pos += 1
            return res[0]
        active = (np.ones(self.slots, bool) if active is None
                  else np.asarray(active, bool))
        if not self.windowed:
            over = np.flatnonzero(active & (self.pos >= self.capacity))
            if over.size:
                raise ValueError(
                    f"KV cache capacity {self.capacity} exhausted for "
                    f"slot(s) {over.tolist()} at "
                    f"pos={self.pos[over].tolist()}; evict or compile a "
                    "longer stream")
        toks = jnp.asarray(tokens)
        if toks.ndim == 2 and toks.shape[-1] == 1 and toks.dtype != jnp.float32:
            toks = toks[:, 0]
        feeds = dict(self.caches)
        feeds["pos"] = jnp.asarray(self.pos, jnp.int32)
        feeds[self._feed_name] = toks
        res = execute(self.compiled, self.params, feeds, cfg=self.cfg,
                      **self.kw)
        self.caches.update(res.cache_updates)
        self.pos = self.pos + active.astype(self.pos.dtype)
        return res[0]

    # --- slot lifecycle (batched streams; the engine's admit/evict) -------

    def _check_slot(self, slot: int) -> None:
        if not self.batched:
            raise ValueError("slot lifecycle applies to batched-slot "
                             "streams (trace_decode(batch=B)) only")
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} out of range [0, {self.slots})")

    def _slot_bank_names(self, slot: int) -> List[str]:
        key = f".slot{slot}."
        return [name for name in self.caches if key in name]

    def _write_banks(self, names: List[str], rows: List[Any]) -> None:
        new = _write_slot(tuple(self.caches[n] for n in names),
                          tuple(_as_arg(r) for r in rows))
        self.caches.update(zip(names, new))

    def reset_slot(self, slot: int) -> None:
        """Recycle one slot: zero its cache banks and position counter."""
        self._check_slot(slot)
        names = self._slot_bank_names(slot)
        with span(SESSION_RESET_SLOT, slot=slot, banks=len(names)):
            self._write_banks(names, [])
        self.pos[slot] = 0

    def load_slot(self, slot: int, kv: Dict[str, jnp.ndarray],
                  n_tokens: int) -> None:
        """Seed one slot from an executed serving prefill: `kv` maps the
        canonical cache names (`ExecResult.kv_exports`) to (S, head_dim)
        rows, written into this slot's banks at positions [0, S), every
        other row of the slot zeroed; the slot's counter starts at
        `n_tokens`."""
        self._check_slot(slot)
        if n_tokens > self.capacity:
            raise ValueError(
                f"prefill of {n_tokens} tokens exceeds the compiled cache "
                f"capacity {self.capacity}")
        loaded: Dict[str, Any] = {}
        for name, rows in kv.items():
            base, leaf = name.rsplit(".", 1)
            bank = f"{base}.slot{slot}.{leaf}"
            if bank not in self.caches:
                raise KeyError(
                    f"no cache bank {bank!r} for export {name!r}")
            loaded[bank] = rows
        names = list(loaded) + [n for n in self._slot_bank_names(slot)
                                if n not in loaded]
        with span(SESSION_LOAD_SLOT, slot=slot, rows=n_tokens,
                  banks=len(names)):
            self._write_banks(names, list(loaded.values()))
        self.pos[slot] = n_tokens

    # --- bucket migration (length-bucketed serving) ------------------------

    def migrate(self, compiled: CompiledProgram) -> int:
        """Move the live session onto a different-capacity compiled stream
        (a bucket crossing in the length-bucketed engine): every cache
        bank's leading rows are copied into a zeroed bank of the new
        capacity, positions and numerics carry over unchanged.  This is
        exact — rows past a slot's position are zeros in the old bank and
        inert under the pos-masked softmax in the new one, so only the
        live prefix matters.  Returns the number of live bank rows moved
        (the MRU/MWU row traffic the engine charges for the crossing)."""
        graph = compiled.graph
        if self.windowed:
            raise ValueError("ring (windowed) streams never migrate — "
                             "the window is the bucket that never grows")
        if set(graph.caches) != set(self.caches):
            raise ValueError(
                "target stream's cache banks do not match this session's "
                "(same model/batch traced at a different capacity required)")
        new_capacity = min(graph.node(nid).shape[-2]
                           for nid in graph.caches.values())
        deepest = int(np.max(self.pos)) if self.batched else int(self.pos)
        if new_capacity < deepest:
            raise ValueError(
                f"cannot migrate to capacity {new_capacity}: slot "
                f"position(s) reach {deepest}")
        moved = 0
        caches: Dict[str, jnp.ndarray] = {}
        with span(SESSION_MIGRATE, capacity=new_capacity):
            for name, nid in graph.caches.items():
                old = self.caches[name]
                shape = graph.node(nid).shape
                lead = old.shape[:len(old.shape) - len(shape)]
                if self.batched:
                    live = self._bank_live_rows(name)
                else:
                    live = deepest
                n = min(live, old.shape[-2], shape[-2])
                buf = jnp.zeros(lead + shape, jnp.float32)
                if n:
                    buf = buf.at[..., :n, :].set(old[..., :n, :])
                caches[name] = buf
                moved += n
        self.caches = caches
        self.compiled = compiled
        self.capacity = new_capacity
        return moved

    def _bank_live_rows(self, name: str) -> int:
        """Rows of bank `name` holding live tokens: the owning slot's
        position (batched banks are named `...slotS.k/v`)."""
        for s in range(self.slots):
            if f".slot{s}." in name:
                return int(self.pos[s])
        return int(np.max(self.pos))
