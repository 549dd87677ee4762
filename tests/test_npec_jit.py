"""The executor runs each compiled graph as one jitted XLA program.

Seven gates:
  * one trace per graph — a decode session's steps at new positions and
    tokens reuse the program traced on the first step, and a second
    engine sharing the stream cache traces nothing;
  * weights are arguments — the lowered program holds no large constant;
  * the trace-time bookkeeping — `peak_live_bytes` and `n_instrs` equal
    what the op-by-op interpreter reported for the same graphs (with
    the 8-bit MMU, a weight counted as its prepared int8 slice);
  * numerics — the jitted program's outputs match the same node loop
    run op by op (`jax.disable_jit`), float and NPE mode;
  * causality — an NPE-mode prefill row's logits do not see the prompt
    rows after it;
  * node marks — one per node, and a weight quantization marked inside
    each matmul that quantizes its weight (bench/tests holds the marks'
    tree in a profile of a served run);
  * slot writes — a session's `load_slot` and `reset_slot` leave the
    banks bitwise as the eager per-bank writes did, each in one traced
    program that donates the slot's banks.
"""
import dataclasses
import re

import numpy as np
import pytest

from repro import npec
from repro.core.overlay import NPEHardware
from repro.npec import exec as exec_mod

HW = NPEHardware(vrwidth=1024)


def _setup(arch):
    import jax
    from repro.configs import get_config
    from repro.models import registry

    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    return cfg, registry.init_params(cfg, jax.random.PRNGKey(0))


def _tokens(cfg, shape):
    import jax
    return jax.random.randint(jax.random.PRNGKey(1), shape, 0,
                              cfg.vocab_size)


@pytest.fixture
def traces(monkeypatch):
    """Counts the executor's traces: `_interpret` runs once per trace."""
    calls = []
    interpret = exec_mod._interpret

    def counted(graph, *a, **kw):
        calls.append(graph)
        return interpret(graph, *a, **kw)
    monkeypatch.setattr(exec_mod, "_interpret", counted)
    return calls


def test_a_decode_session_traces_its_stream_once(traces):
    cfg, params = _setup("glm4_9b")
    prog = npec.compile_decode(cfg, 8, HW, bits=8, batch=2)
    sess = npec.DecodeSession(prog, params,
                              cfg=cfg.with_npe(quant_bits=8, segments=16))
    for t in range(5):
        sess.step(np.array([3 + t, 11 * t + 1], np.int32),
                  active=np.array([True, t % 2 == 0]))
    assert traces == [prog.graph]
    assert sess.pos.tolist() == [5, 3]


def test_engines_sharing_a_stream_cache_share_the_programs(traces):
    from repro.npec.runtime import NPEEngine

    cfg, params = _setup("glm4_9b")

    def serve(**kw):
        eng = NPEEngine(cfg, slots=2, capacity=16, max_new_tokens=3,
                        bits=8, npe=True, params=params, **kw)
        for n in (5, 7, 5):
            eng.submit(list(range(1, n + 1)))
        eng.run()
        return eng

    first = serve()
    # one trace per distinct program: two prefill lengths and the decode
    assert len(traces) == len(set(map(id, traces))) == 3
    second = serve(stream_cache=first.stream_cache)
    assert len(traces) == 3
    assert ([r.generated for r in second.stats.requests]
            == [r.generated for r in first.stats.requests])


def _constant_bytes(hlo: str):
    """Bytes of every constant in a lowered StableHLO module."""
    size = {"f32": 4, "i32": 4, "ui32": 4, "bf16": 2, "f16": 2, "i8": 1,
            "i1": 1, "i64": 8, "f64": 8, "ui8": 1, "i16": 2}
    out = []
    for m in re.finditer(r"stablehlo\.constant dense<.*?> : "
                         r"tensor<((?:\d+x)*)(\w+)>", hlo):
        dims = [int(d) for d in m.group(1).split("x") if d]
        out.append(int(np.prod(dims, dtype=np.int64)) * size[m.group(2)])
    return out


@pytest.mark.parametrize("npe", [False, True])
def test_weights_are_arguments_not_constants(npe):
    import jax

    cfg, params = _setup("bert_base")
    ncfg = cfg.with_npe(quant_bits=8, segments=16) if npe else cfg
    prog = npec.compile_model(cfg, 32, HW, bits=8 if npe else 16)
    exe = exec_mod.executable(prog.graph, npe_quant=ncfg.npe_quant,
                              bits=ncfg.npe_quant_bits,
                              use_pwl=ncfg.npe_pwl,
                              segments=ncfg.npe_pwl_segments)
    hlo = exe.fn.lower(params, {"tokens": _tokens(cfg, (2, 32))}).as_text()
    consts = _constant_bytes(hlo)
    assert consts                       # the pattern reads this module
    assert max(consts) <= 64 * 1024
    # a weight folded in as a constant would have shown
    assert max(w.nbytes for w in jax.tree.leaves(params)) > 64 * 1024


# peak_live_bytes and n_instrs of the op-by-op interpreter before the
# executor was jitted, on the same graphs and feeds
GOLDEN = {"bert_base": (262400, 53), "glm4_9b": (295936, 68)}
# with the 8-bit MMU a weight's value is its prepared int8 slice and
# scales: glm4's peak, the float32 head, falls to the embedding's table
# (bert's peak was its table already)
GOLDEN_INT8 = dict(GOLDEN, glm4_9b=(262168, 68))


@pytest.mark.parametrize("arch", sorted(GOLDEN))
@pytest.mark.parametrize("npe", [False, True])
def test_trace_time_bookkeeping_is_exact(arch, npe):
    import jax.numpy as jnp

    cfg, params = _setup(arch)
    ncfg = cfg.with_npe(quant_bits=8, segments=16) if npe else cfg
    bits = 8 if npe else 16
    if arch == "bert_base":
        prog = npec.compile_model(cfg, 32, HW, bits=bits)
        feeds = [{"tokens": _tokens(cfg, (2, 32))}] * 2
    else:
        prog = npec.compile_decode(cfg, 16, HW, bits=bits, batch=2)
        banks = {name: jnp.zeros(prog.graph.node(nid).shape, jnp.float32)
                 for name, nid in prog.graph.caches.items()}
        feeds = [dict(banks, pos=jnp.asarray([p, 2 * p], jnp.int32),
                      tokens=np.array([1 + p, 2], np.int32))
                 for p in (3, 4)]
    for f in feeds:                     # traced, then the cached program
        res = npec.execute(prog, params, f, cfg=ncfg)
        golden = GOLDEN_INT8 if npe else GOLDEN
        assert (res.peak_live_bytes, res.n_instrs) == golden[arch]


def _run(arch, mode):
    """The first output of each of a graph's executions, stacked."""
    cfg, params = _setup(arch)
    ncfg = cfg.with_npe(quant_bits=8, segments=16) if mode == "npe" else cfg
    bits = 8 if mode == "npe" else 16
    if arch == "glm4_9b":
        prog = npec.compile_decode(cfg, 16, HW, bits=bits, batch=2)
        sess = npec.DecodeSession(prog, params, cfg=ncfg)
        return np.stack([np.asarray(sess.step(np.array([3 + t, 7 * t + 1],
                                                       np.int32)))
                         for t in range(3)])
    seq = 32 if arch == "bert_base" else 8
    prog = npec.compile_model(cfg, seq, HW, bits=bits)
    return np.asarray(npec.execute(prog, params,
                                   {"tokens": _tokens(cfg, (2, seq))},
                                   cfg=ncfg)[0])


@pytest.mark.parametrize("mode", ["float", "npe"])
@pytest.mark.parametrize("arch", ["bert_base", "glm4_9b",
                                  "granite_moe_1b_a400m"])
def test_jitted_outputs_match_the_op_by_op_interpreter(arch, mode, tol_for):
    """The tolerance is the conformance suite's, on the outputs' scale:
    XLA contracts a multiply and an add into one FMA inside a fused
    program, which moves float32 results by a few ulps (the gap is 0
    with FMA instructions off, `--xla_cpu_max_isa=SSE4_2`)."""
    import jax

    got = _run(arch, mode)
    with jax.disable_jit():
        want = _run(arch, mode)
    err = float(np.max(np.abs(got - want)))
    assert err <= tol_for(mode) * max(1.0, float(np.max(np.abs(want)))), err


@pytest.mark.parametrize("arch", ["bert_base", "glm4_9b"])
def test_npe_prefill_is_causal(arch):
    """Replacing an int8 NPE-mode prefill's prompt from row i on leaves
    the logits of rows 0..i-1 as they were: each MMU row is quantized
    with its own scale, and the masked softmax gives later keys weight
    exactly 0."""
    cfg, params = _setup(arch)
    S, i = 12, 5
    prog = npec.compile_prefill(cfg, S, HW, bits=8)
    ncfg = cfg.with_npe(quant_bits=8, segments=16)
    a = np.asarray(_tokens(cfg, (S,)), np.int32)
    b = a.copy()
    b[i:] = (a[i:] + 1 + np.arange(S - i)) % cfg.vocab_size
    la, lb = (np.asarray(npec.execute(prog, params, {"tokens": t},
                                      cfg=ncfg)[0]) for t in (a, b))
    assert float(np.max(np.abs(la[:i] - lb[:i]))) <= 1e-6
    assert float(np.max(np.abs(la[i:] - lb[i:]))) > 0.0


@pytest.mark.parametrize("npe", [False, True])
def test_node_marks_follow_the_graph(npe):
    from repro.npec.obs import EXEC_PREFIX, node_class

    cfg, _ = _setup("granite_moe_1b_a400m")
    graph = npec.compile_model(cfg, 8, HW, bits=8).graph
    marks = exec_mod._marks(graph, npe)
    assert [name for name, _ in marks] == [
        EXEC_PREFIX + node_class(graph, n) for n in graph.nodes]
    mmu = [n for n in graph.nodes if node_class(graph, n) == "mmu"]
    pinned = [n for n in mmu if not n.attrs.get("quantize", True)]
    assert pinned                       # the router and expert streams
    assert sum(q for _, q in marks) == (len(mmu) - len(pinned)) * npe


def _slot_session(arch, capacity, slots=3):
    """An NPE-mode batched session whose banks all hold decoded rows."""
    cfg, params = _setup(arch)
    ncfg = cfg.with_npe(quant_bits=8, segments=16)
    prog = npec.compile_decode(cfg, capacity, HW, bits=8, batch=slots)
    sess = npec.DecodeSession(prog, params, cfg=ncfg)
    for t in range(2):
        sess.step(np.arange(3 + t, 3 + t + slots, dtype=np.int32))
    return cfg, params, ncfg, sess


def _exports(cfg, params, ncfg, S):
    prog = npec.compile_prefill(cfg, S, HW, bits=8)
    toks = np.asarray(_tokens(cfg, (S,)), np.int32)
    return npec.execute(prog, params, {"tokens": toks}, cfg=ncfg).kv_exports


def _eager_load(banks, slot, kv):
    """The slot's banks as the eager per-bank writes left them: each
    zeroed, then each exported bank's rows [0, S) set."""
    import jax.numpy as jnp

    key = f".slot{slot}."
    out = {n: jnp.zeros_like(b) if key in n else b for n, b in banks.items()}
    for name, rows in kv.items():
        base, leaf = name.rsplit(".", 1)
        bank = f"{base}.slot{slot}.{leaf}"
        arr = jnp.asarray(rows, jnp.float32)
        arr = arr.reshape(arr.shape[-2:])
        out[bank] = out[bank].at[: arr.shape[0]].set(arr)
    return out


def _assert_bitwise(got, want):
    assert set(got) == set(want)
    for name in want:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.dtype == w.dtype == np.float32, name
        assert np.array_equal(g.view(np.uint32), w.view(np.uint32)), name


@pytest.mark.parametrize("arch", ["bert_base", "glm4_9b"])
@pytest.mark.parametrize("first,second", [(1, 9), (9, 16), (16, 1)])
def test_slot_writes_equal_the_eager_bank_writes(arch, first, second):
    """Load slot 1 of three at `first` rows (1, a middle length, the
    capacity 16), reset it, load it again at `second` from host rows, as
    a chunked admit passes them: each time the slot's banks are bitwise
    the eager writes' and the other slots' banks and counters hold."""
    cfg, params, ncfg, sess = _slot_session(arch, 16)
    banks = {n: np.asarray(b) for n, b in sess.caches.items()}
    assert all(np.any(b != 0) for b in banks.values())
    kv = _exports(cfg, params, ncfg, first)
    sess.load_slot(1, kv, first)
    _assert_bitwise(sess.caches, _eager_load(banks, 1, kv))
    assert sess.pos.tolist() == [2, first, 2]
    sess.reset_slot(1)
    _assert_bitwise(sess.caches, _eager_load(banks, 1, {}))
    assert sess.pos.tolist() == [2, 0, 2]
    kv = {n: np.asarray(r)
          for n, r in _exports(cfg, params, ncfg, second).items()}
    sess.load_slot(1, kv, second)
    _assert_bitwise(sess.caches, _eager_load(banks, 1, kv))
    assert sess.pos.tolist() == [2, second, 2]


def test_a_slot_write_is_one_donated_dispatch(monkeypatch):
    """Loads at one length trace the slot writer once, resets once more;
    each write deletes the slot's old banks and reuses their buffers;
    the session spans and the trace span name the banks written."""
    from repro.npec.obs import (EXEC_TRACE, SESSION_LOAD_SLOT,
                                SESSION_RESET_SLOT)

    cfg, params, ncfg, sess = _slot_session("glm4_9b", 24)
    kv = _exports(cfg, params, ncfg, 5)
    opened = []
    span = exec_mod.span

    def recorded(name, **kw):
        opened.append((name, kw))
        return span(name, **kw)
    recorded.is_enabled = span.is_enabled
    monkeypatch.setattr(exec_mod, "span", recorded)
    exec_mod._write_slot.clear_cache()

    def write(fn, slot, *args):
        names = [n for n in sess.caches if f".slot{slot}." in n]
        old = [sess.caches[n] for n in names]
        buffers = {b.unsafe_buffer_pointer() for b in old}
        fn(slot, *args)
        assert all(b.is_deleted() for b in old)
        assert buffers == {sess.caches[n].unsafe_buffer_pointer()
                           for n in names}
        return len(names)

    n = write(sess.load_slot, 0, kv, 5)
    assert n == len(kv) == len(sess.caches) // 3
    for slot in (1, 0, 2):
        write(sess.load_slot, slot, kv, 5)
    for slot in (0, 2):
        write(sess.reset_slot, slot)
    assert [kw for name, kw in opened if name == EXEC_TRACE] == [
        {"rows": 5, "banks": n}, {"rows": 0, "banks": n}]
    assert [kw for name, kw in opened if name == SESSION_LOAD_SLOT] == [
        {"slot": s, "rows": 5, "banks": n} for s in (0, 1, 0, 2)]
    assert [kw for name, kw in opened if name == SESSION_RESET_SLOT] == [
        {"slot": s, "banks": n} for s in (0, 2)]
    assert exec_mod._write_slot._cache_size() == 2
