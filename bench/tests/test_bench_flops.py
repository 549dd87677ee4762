"""Operation counts against hand counts for both configurations."""
import json

import pytest

import bench_tiny  # noqa: F401
import flops
import spec


def dims(name):
    cfg = json.loads((spec.BENCH_DIR / "configs" / f"{name}.json").read_text())
    return spec.reference_module(name).dims(cfg)


def test_bert_base_prefill_by_hand():
    d = dims("bert_base")
    assert d == flops.Dims(12, 768, 12, 12, 64, 3072, 30522, False)
    per_row = 4 * 768 * 768 + 2 * 768 * 3072           # qkv, out, two MLP
    seq = 128
    attn = 4 * 12 * 64 * (seq * (seq + 1) // 2)        # QK^T and AV, causal
    want = 12 * (2 * seq * per_row + attn) + 2 * 768 * 30522
    assert flops.prefill_ops(d, seq) == want
    assert 21.7e9 < want < 22.2e9                     # ~22 GFLOP at 128 rows


def test_glm4_9b_stage_decode_by_hand():
    d = dims("glm4_9b")
    assert d == flops.Dims(5, 4096, 32, 2, 128, 13696, 151552, True)
    per_row = (4096 * (4096 + 2 * 256) + 4096 * 4096
               + 3 * 4096 * 13696)
    keys = 1000
    want = 5 * (2 * per_row + 4 * 32 * 128 * keys) + 2 * 4096 * 151552
    assert flops.decode_ops(d, keys) == want
    assert 3.2e9 < want < 3.4e9                       # ~3.3 G a token


@pytest.mark.parametrize("name", ["bert_base", "glm4_9b"])
def test_prefill_of_one_token_is_one_decode_over_one_key(name):
    d = dims(name)
    assert flops.prefill_ops(d, 1) == flops.decode_ops(d, 1)


def test_prefill_counts_the_head_once():
    d = dims("bert_base")
    grow = flops.prefill_ops(d, 2) - flops.prefill_ops(d, 1)
    assert grow == d.layers * (2 * d.layer_weights()
                               + flops.attention_ops(d, 2))
