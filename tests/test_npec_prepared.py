"""Weights prepared once: with the 8-bit MMU every weight a program's
matmuls quantize is quantized when the weights are prepared
(`exec.prepare_weights`), not inside every execution.

Two gates:
  * bitwise — a prepared tree, and a raw tree prepared inside the
    program, give exactly what the per-call path gives (each param cast
    to float32 and each weight quantized inside its matmul): the outputs,
    `cache_updates` and `kv_exports` of whole and chunked prefill, of
    batched `DecodeSession` steps, and an engine's token streams.  Tiny
    configs of both families: a tied head (`bert_base`) and an untied
    one (`glm4_9b`), both with qkv bias and grouped kv heads, bf16
    weights;
  * structural — a decode program handed a prepared tree holds no
    float32 copy of the embedding and rounds no weight, and an engine
    prepares its weights once, at construction, and never while serving.
"""
import re

import numpy as np
import pytest

from repro import npec
from repro.core.overlay import NPEHardware
from repro.npec import exec as exec_mod

HW = NPEHardware(vrwidth=1024)
S, C, T, B = 11, 4, 24, 2            # prompt, chunk, capacity, decode slots


def _setup(arch):
    """A tiny config and its weights in bf16, as the benchmark draws them."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import registry

    cfg = get_config(arch, smoke=True)
    assert cfg.qkv_bias and cfg.num_kv_heads < cfg.num_heads
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)


def _prompt(cfg, n, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _arrays(res):
    """Every array an execution returned, by name."""
    out = {f"out{i}": np.asarray(v) for i, v in enumerate(res.outputs)}
    out.update({f"upd:{k}": np.asarray(v)
                for k, v in (res.cache_updates or {}).items()})
    out.update({f"kv:{k}": np.asarray(v)
                for k, v in (res.kv_exports or {}).items()})
    return out


def _prefill(cfg, ncfg, weights):
    prog = npec.compile_prefill(cfg, S, HW, bits=8)
    return _arrays(npec.execute(prog, weights, {"tokens": _prompt(cfg, S)},
                                cfg=ncfg))


def _chunked(cfg, ncfg, weights):
    out, prompt = {}, _prompt(cfg, S)
    caches = None
    for base in range(0, S, C):
        rows = min(C, S - base)
        prog = npec.compile_prefill(cfg, rows, HW, bits=8, cache_len=T)
        if caches is None:
            caches = {name: np.zeros(prog.graph.node(nid).shape, np.float32)
                      for name, nid in prog.graph.caches.items()}
        feeds = dict(caches, tokens=prompt[base:base + rows],
                     pos_ids=np.arange(base, base + rows, dtype=np.int32))
        res = npec.execute(prog, weights, feeds, cfg=ncfg)
        caches = {k: np.asarray(v) for k, v in res.cache_updates.items()}
        out.update({f"{base}/{k}": v for k, v in _arrays(res).items()})
    return out


def _decode(cfg, ncfg, weights):
    pre = npec.execute(npec.compile_prefill(cfg, S, HW, bits=8), weights,
                       {"tokens": _prompt(cfg, S)}, cfg=ncfg)
    sess = npec.DecodeSession(npec.compile_decode(cfg, T, HW, bits=8,
                                                  batch=B),
                              weights, cfg=ncfg)
    sess.load_slot(1, pre.kv_exports, S)
    out = {}
    for t in range(5):
        toks = np.array([3 + t, 7 * t + 1], np.int32)
        out[f"step{t}"] = np.asarray(sess.step(
            toks, active=np.array([True, t != 2])))
    out.update({f"bank:{k}": np.asarray(v) for k, v in sess.caches.items()})
    return out


def _engine(cfg, ncfg, weights):
    from repro.npec.runtime import NPEEngine

    eng = NPEEngine(cfg, slots=B, capacity=T, max_new_tokens=6, bits=8,
                    npe=True, params=weights)
    for n in (5, 9, 7):
        eng.submit(_prompt(cfg, n, seed=n))
    eng.run()
    return {f"req{r.rid}": np.asarray(r.generated)
            for r in eng.stats.requests}


PHASES = {"prefill": _prefill, "chunked": _chunked, "decode": _decode,
          "engine": _engine}


def _per_call(monkeypatch):
    """The path before preparation: every param a float32 slice, every
    weight quantized inside its matmul, every table gathered in float32."""
    monkeypatch.setattr(exec_mod, "_param_roles", lambda *a, **k: {})


@pytest.mark.parametrize("phase", sorted(PHASES))
@pytest.mark.parametrize("arch", ["bert_base", "glm4_9b"])
def test_prepared_weights_are_bitwise_the_per_call_path(arch, phase):
    cfg, params = _setup(arch)
    ncfg = cfg.with_npe(quant_bits=8, segments=16)
    run = PHASES[phase]
    got = {}
    if phase != "engine":             # an engine prepares what it is given
        got["raw"] = run(cfg, ncfg, params)
        graph = npec.compile_decode(cfg, T, HW, bits=8, batch=B).graph
        prepared = exec_mod.prepare_weights(params, graph, cfg=ncfg)
        assert set(prepared[exec_mod.PREPARED]) >= {"blocks/wq",
                                                    "blocks/wo"}
        got["prepared"] = run(cfg, ncfg, prepared)
    else:
        got["engine"] = run(cfg, ncfg, params)
    with pytest.MonkeyPatch.context() as mp:
        _per_call(mp)
        want = run(cfg, ncfg, params)
    for name, arrays in got.items():
        assert arrays.keys() == want.keys(), name
        for key, value in want.items():
            assert np.array_equal(arrays[key], value), (name, key)


def test_the_tied_head_has_its_own_prepared_leaf():
    """`bert_base` gathers from `embed` and projects onto `embed.T`: the
    gather keeps the bf16 table, the head gets an int8 leaf with one
    scale per vocabulary row."""
    cfg, params = _setup("bert_base")
    graph = npec.compile_decode(cfg, T, HW, bits=8, batch=B).graph
    prepared = exec_mod.prepare_weights(
        params, graph, cfg=cfg.with_npe(quant_bits=8, segments=16))
    head = prepared[exec_mod.PREPARED]["embed.T"]
    assert head.q.shape == (cfg.d_model, cfg.vocab_size)
    assert head.q.dtype == np.int8 and head.scale.shape == (1, cfg.vocab_size)
    assert prepared["embed"] is params["embed"]
    assert "embed" not in prepared[exec_mod.PREPARED]


def test_float_and_sixteen_bit_numerics_prepare_nothing():
    cfg, params = _setup("glm4_9b")
    graph = npec.compile_decode(cfg, T, HW, bits=16, batch=B).graph
    for ncfg in (None, cfg.with_npe(quant_bits=16, segments=16)):
        assert exec_mod.prepare_weights(params, graph, cfg=ncfg) is params


def test_a_prepared_tree_passes_through():
    cfg, params = _setup("glm4_9b")
    ncfg = cfg.with_npe(quant_bits=8, segments=16)
    graph = npec.compile_decode(cfg, T, HW, bits=8, batch=B).graph
    prepared = exec_mod.prepare_weights(params, graph, cfg=ncfg)
    assert exec_mod.prepare_weights(prepared, graph, cfg=ncfg) is prepared


def _round_shapes(hlo: str):
    return {tuple(int(d) for d in m.group(1).split("x"))
            for m in re.finditer(r"stablehlo\.round_nearest_even [^\n]*?: "
                                 r"tensor<((?:\d+x)*\d+)xf32>", hlo)}


def test_a_prepared_decode_program_holds_no_weight_preparation():
    """The lowered decode program over a prepared tree has no float32
    (V, H) embedding and rounds only activations; over the raw tree the
    same program still quantizes its weights inside, as the pattern
    shows."""
    cfg, params = _setup("glm4_9b")
    ncfg = cfg.with_npe(quant_bits=8, segments=16)
    prog = npec.compile_decode(cfg, T, HW, bits=8, batch=B)
    graph = prog.graph
    exe = exec_mod.executable(graph, npe_quant=True, bits=8,
                              use_pwl=ncfg.npe_pwl,
                              segments=ncfg.npe_pwl_segments)
    feeds = {name: np.zeros(graph.node(nid).shape, np.float32)
             for name, nid in graph.caches.items()}
    feeds.update(pos=np.array([3, 5], np.int32),
                 tokens=np.array([1, 2], np.int32))
    weight_shapes = set()
    for n in graph.nodes:
        if (n.op == "matmul" and graph.node(n.inputs[1]).op == "param"
                and n.attrs.get("quantize", True)):
            w = graph.node(n.inputs[1]).shape
            weight_shapes |= {tuple(w), tuple(w[::-1])}
    for leaf in params["blocks"].values():
        if hasattr(leaf, "shape") and leaf.ndim == 3:
            weight_shapes.add(tuple(leaf.shape))
    table = f"tensor<{cfg.vocab_size}x{cfg.d_model}xf32>"

    prepared = exec_mod.prepare_weights(params, graph, cfg=ncfg)
    hlo = exe.fn.lower(prepared, feeds).as_text()
    rounds = _round_shapes(hlo)
    assert rounds                      # the activations' own quantization
    assert not rounds & weight_shapes, rounds & weight_shapes
    assert table not in hlo

    raw = exe.fn.lower(params, feeds).as_text()
    assert _round_shapes(raw) & weight_shapes
    assert table not in raw            # the gather reads the bf16 table


def test_an_engine_prepares_once_and_never_while_serving(monkeypatch):
    from repro.npec.runtime import NPEEngine

    opened = []
    span = exec_mod.span

    def recording(name, **kw):
        opened.append(name)
        return span(name, **kw)
    recording.is_enabled = span.is_enabled
    monkeypatch.setattr(exec_mod, "span", recording)

    cfg, params = _setup("glm4_9b")
    eng = NPEEngine(cfg, slots=B, capacity=T, max_new_tokens=4, bits=8,
                    npe=True, params=params)
    assert opened.count(exec_mod.EXEC_PREPARE_WEIGHTS) == 1
    opened.clear()
    for n in (5, 9, 7):
        eng.submit(_prompt(cfg, n, seed=n))
    eng.run()
    assert opened.count(exec_mod.EXEC_EXECUTE) > 3   # it did serve
    assert exec_mod.EXEC_PREPARE_WEIGHTS not in opened
