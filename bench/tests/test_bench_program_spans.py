"""The program's wall-clock spans: the tree a numeric engine emits under
the profiler, and their reduction (`program_spans.py`) on hand-made
events."""
import dataclasses
from collections import Counter
from types import SimpleNamespace

import pytest

import bench_tiny  # noqa: F401
import program_spans as ps
import spec
import trace_reduce as tr
from trace_reduce import Event, Trace

# -- the program's side ----------------------------------------------------


def test_names_spelled_out_are_the_programs():
    from repro.npec import obs

    assert (ps.ADMIT, ps.DECODE, ps.SYNC, ps.LOAD_SLOT, ps.EXECUTE,
            ps.QUANTIZE_WEIGHT, ps.EXEC_PREFIX) == (
        obs.ENGINE_ADMIT, obs.ENGINE_DECODE, obs.ENGINE_SYNC,
        obs.SESSION_LOAD_SLOT, obs.EXEC_EXECUTE, obs.EXEC_QUANTIZE_WEIGHT,
        obs.EXEC_PREFIX)
    assert set(ps.CLASSES) == set(obs.OP_CLASS.values()) | {"attention"}
    assert ps.PARAM == obs.EXEC_PREFIX + obs.OP_CLASS["param"]
    assert all(name.startswith(ps.PREFIX) for name in (
        obs.SESSION_RESET_SLOT, obs.SESSION_MIGRATE))


def test_every_ir_op_has_a_class():
    from repro.npec import ir
    from repro.npec.obs import OP_CLASS

    assert set(OP_CLASS) == set(ir.COMPUTE_OPS + ir.FOLDED_OPS
                                + ir.MEMORY_OPS)


def _node_classes(graph) -> Counter:
    """Expected dispatch spans of one execution of `graph`, by name."""
    from repro.npec.obs import OP_CLASS

    out = Counter()
    for n in graph.nodes:
        cls = OP_CLASS[n.op]
        if n.op == "matmul" and graph.node(n.inputs[1]).op != "param":
            cls = "attention"
        out[ps.EXEC_PREFIX + cls] += 1
    return out


def _traced_engine(tmp_path, arch, chunk):
    import jax
    from repro.configs import get_config
    from repro.models import registry
    from repro.npec.runtime import NPEEngine

    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    eng = NPEEngine(cfg, slots=2, capacity=32, max_new_tokens=3, bits=8,
                    npe=True, params=registry.init_params(
                        cfg, jax.random.PRNGKey(0)),
                    prefill_chunk=chunk)
    for n in (5, 7, 5):
        eng.submit(list(range(1, n + 1)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    return eng, tr.read_xplane(tr.find_xplane(str(tmp_path)))


@pytest.mark.parametrize("arch,chunk", [("bert_base", None),
                                        ("glm4_9b", None), ("glm4_9b", 4)])
def test_span_tree_of_a_served_run(tmp_path, arch, chunk):
    from repro.npec.runtime.engine import chunk_spans

    eng, trace = _traced_engine(tmp_path, arch, chunk)
    prog, _ = ps.split(trace.host)
    spans = sorted(prog, key=lambda e: (e.start, -e.end))
    parent = ps._parents(spans)

    def ancestors(i):
        while parent[i] is not None:
            i = parent[i]
            yield spans[i].name

    classes = {ps.EXEC_PREFIX + c for c in ps.CLASSES}
    for i, e in enumerate(spans):
        if e.name in classes:
            up = list(ancestors(i))
            assert ps.EXECUTE in up, (e.name, up)
            assert {ps.ADMIT, ps.DECODE} & set(up), (e.name, up)
        elif e.name == ps.QUANTIZE_WEIGHT:
            assert spans[parent[i]].name == ps.EXEC_PREFIX + "mmu"
        elif e.name == ps.EXECUTE:
            assert {ps.ADMIT, ps.DECODE} & set(ancestors(i))

    want = Counter()
    for req in eng.stats.requests:
        for _, rows in chunk_spans(len(req.prompt), chunk):
            want += _node_classes(eng._prefill_program(rows).graph)
    decode = _node_classes(eng.decode_prog.graph)
    for name in decode:
        want[name] += decode[name] * eng.stats.decode_steps
    got = Counter(e.name for e in spans if e.name in classes)
    assert got == want
    counts = Counter(e.name for e in spans)
    assert counts[ps.LOAD_SLOT] == eng.stats.prefills == 3
    assert counts[ps.DECODE] == eng.stats.decode_steps
    assert counts[ps.QUANTIZE_WEIGHT] == want[ps.EXEC_PREFIX + "mmu"]

    p = ps.reduce(trace)
    assert all(read(p) is not None for read in ps.READINGS.values())
    lines = ps.report(trace)
    assert any(line.startswith("npec.exec.mmu ") for line in lines)


# -- the reduction, on hand-made events ------------------------------------

BENCH = [Event("bench.step.admit", 0, 100),
         Event("bench.step.decode", 100, 200)]
PROGRAM = [
    Event(ps.ADMIT, 10, 90),
    Event(ps.EXECUTE, 12, 60),
    Event(ps.PARAM, 12, 20),
    Event("npec.exec.mmu", 20, 40),
    Event(ps.QUANTIZE_WEIGHT, 22, 30),
    Event("npec.exec.nvu", 40, 60),
    Event(ps.LOAD_SLOT, 60, 80),
    Event("npec.session.reset_slot", 60, 65),
    Event(ps.SYNC, 80, 90),
    Event(ps.DECODE, 110, 190),
    Event(ps.EXECUTE, 110, 170),
    Event(ps.PARAM, 110, 150),
    Event("npec.exec.attention", 150, 170),
    Event(ps.SYNC, 170, 190),
    Event(ps.ADMIT, 300, 310),                     # outside the window
]
OTHER = [Event("Allocate", 150, 160), Event(ps.COMPILE, 50, 55),
         Event(ps.COMPILE, 400, 410)]
OPS = [Event("q", 25, 35), Event("n", 45, 50), Event("z", 62, 64),
       Event("idle", 95, 99), Event("w", 145, 149), Event("a", 155, 158),
       Event("s", 172, 180)]


def _trace(program=PROGRAM):
    return Trace({"/device:TPU:0": OPS}, BENCH, OTHER + program)


def test_self_time_and_nesting():
    p = ps.reduce(_trace())
    assert p.spans.count == {
        ps.ADMIT: 1, ps.EXECUTE: 2, ps.PARAM: 2, "npec.exec.mmu": 1,
        ps.QUANTIZE_WEIGHT: 1, "npec.exec.nvu": 1, ps.LOAD_SLOT: 1,
        "npec.session.reset_slot": 1, ps.SYNC: 2, ps.DECODE: 1,
        "npec.exec.attention": 1}
    assert p.spans.total_ns[ps.ADMIT] == 80
    assert p.spans.self_ns[ps.ADMIT] == 80 - 48 - 20 - 10
    assert p.spans.self_ns["npec.exec.mmu"] == 12
    assert p.spans.self_ns[ps.LOAD_SLOT] == 15
    assert p.spans.self_ns[ps.EXECUTE] == 0
    assert p.spans.self_ns[ps.DECODE] == 0
    admit, decode = p.under[ps.ADMIT], p.under[ps.DECODE]
    assert ps.ADMIT not in admit.count
    assert admit.total_ns == {
        ps.EXECUTE: 48, ps.PARAM: 8, "npec.exec.mmu": 20,
        ps.QUANTIZE_WEIGHT: 8, "npec.exec.nvu": 20, ps.LOAD_SLOT: 20,
        "npec.session.reset_slot": 5, ps.SYNC: 10}
    assert decode.total_ns == {ps.EXECUTE: 60, ps.PARAM: 40,
                               "npec.exec.attention": 20, ps.SYNC: 20}
    assert p.compiles == 1


def test_device_time_goes_to_the_innermost_open_span():
    p = ps.reduce(_trace())
    assert p.device_ns == {ps.QUANTIZE_WEIGHT: 10, "npec.exec.nvu": 5,
                           "npec.session.reset_slot": 2, ps.PARAM: 4,
                           "npec.exec.attention": 3, ps.SYNC: 8}


@pytest.mark.parametrize("name,want", [
    ("prefill_exec_ms", 48e-6), ("load_slot_ms", 20e-6),
    ("first_token_sync_ms", 10e-6), ("weight_prep_share.prefill", 20.0),
    ("weight_prep_share.decode", 50.0), ("decode_sync_ms", 20e-6)])
def test_readings(name, want):
    read = ps.READINGS[name]
    assert read(ps.reduce(_trace())) == pytest.approx(want)
    unrelated = ps.reduce(_trace([Event("npec.session.migrate", 5, 8)]))
    assert read(unrelated) is None


def test_no_program_spans_reduce_to_nothing():
    assert ps.reduce(_trace([])) is None
    assert ps.report(_trace([])) == [
        "program spans: none in the traced window"]


def test_labels_with_and_without_program_spans():
    assert ps.label(BENCH, PROGRAM, OTHER, 155) == (
        "bench.step.decode / npec.exec.attention / Allocate")
    assert ps.label(BENCH, PROGRAM, OTHER, 185) == (
        "bench.step.decode / npec.engine.sync")
    assert ps.label(BENCH, PROGRAM, OTHER, 95) == "bench.step.admit"
    for t in (5, 95, 155, 185, 250):
        assert ps.label(BENCH, [], OTHER, t) == tr._label(BENCH, OTHER, t)
    assert ps.top_gaps(_trace())[:2] == [
        ("bench.step.decode / npec.exec.param", pytest.approx(46e-9)),
        ("bench.step.admit / npec.session.load_slot", pytest.approx(31e-9))]


def test_program_spans_move_no_existing_number():
    """`trace_reduce` files the spans among the host events; every number
    of its summary, and every accepted reader, reads the same."""
    plain = tr.summarize(_trace([]))
    spanned = tr.summarize(_trace())
    for f in dataclasses.fields(plain):
        if f.name != "top_gaps":
            assert getattr(plain, f.name) == getattr(spanned, f.name)
    assert [s for _, s in plain.top_gaps] == [s for _, s in spanned.top_gaps]
    timeline = SimpleNamespace(requests=[], t_open=0.0, t_close=1.0)
    for m in spec.load_benchmark()["per_layer"]:
        read = spec.metric_reader(m["name"])
        ctx = [SimpleNamespace(trace=s, timeline=timeline, prefills=3,
                               decode_steps=2, dims=None, flops=None,
                               peak_ops=1.0) for s in (plain, spanned)]
        assert read(ctx[0]) == read(ctx[1]), m["name"]


def test_the_command_refuses_without_a_tpu_and_restores_the_reader():
    read = tr.read_xplane
    assert ps.main(["--workload", "bert_base.encode", "--seed",
                    "2147483653", "--seconds", "1"]) == 2
    assert tr.read_xplane is read
