"""The configurations as run: every published width kept, the served
parameter tree's shapes, and the reference against the program's float
path at a tiny size."""
import dataclasses
import json

import jax
import numpy as np
import pytest

import bench_tiny
import check
import run
import spec

NAMES = ["bert_base", "glm4_9b"]
WIDTHS = {"hidden_size", "num_attention_heads", "intermediate_size",
          "ffn_hidden_size", "kv_channels", "multi_query_group_num"}


def config(name):
    return json.loads((spec.BENCH_DIR / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", NAMES)
def test_cut_keeps_every_published_width(name):
    c = config(name)
    cfg = run.program_config(c)           # raises on any stated mismatch
    assert not WIDTHS & set(c["reduced"])
    for key in c["reduced"]:
        assert c["published"][key] != c[key]
    d = spec.reference_module(name).dims(c)
    assert (d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff) == (
        cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.d_ff)


def test_glm4_9b_is_one_stage_of_forty_layers():
    c = config("glm4_9b")
    assert c["published"]["num_layers"] == 40 and c["num_layers"] == 5
    assert run.program_config(c).num_layers == 5


@pytest.mark.parametrize("name", NAMES)
def test_layout_matches_the_served_parameter_tree(name):
    from repro.models import registry

    c = config(name)
    cfg = run.program_config(c)
    want = jax.tree_util.tree_flatten_with_path(
        registry.abstract_params(cfg))[0]
    want = {jax.tree_util.keystr(k): v.shape for k, v in want}
    got = jax.tree_util.tree_flatten_with_path(
        spec.reference_module(name).layout(c),
        is_leaf=lambda x: hasattr(x, "std"))[0]
    got = {jax.tree_util.keystr(k): v.shape for k, v in got}
    assert set(got) <= set(want)
    assert all(got[k] == want[k] for k in got)
    assert set(want) - set(got) <= {"['pooler']"}


@pytest.mark.parametrize("cell_name", ["bert_base.encode", "glm4_9b.chat"])
def test_reference_agrees_with_the_float_program(cell_name):
    """With NPE numerics off the program computes in float32 as the
    reference does, so every served token is the reference's own choice
    up to rounding."""
    cell = bench_tiny.tiny_cell(cell_name)
    cell.config["numerics"] = dict(cell.config["numerics"], npe=False)
    b = run.build(cell, 5, clock=bench_tiny.Ticks())
    tl = b.loop.run(2.0)
    items = run.served_items(b, tl)
    gaps, _ = check.gaps(b.ref, cell.config, b.params, items,
                         pad=cell.traffic["capacity"])
    assert gaps.size >= len(items) >= 1
    assert float(gaps.max()) < 1e-4
