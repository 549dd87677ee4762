"""The load generator: seeded, the same mix of sizes for every seed."""
import itertools
from collections import Counter

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import loadgen
import spec

SEEDS = [0, 7, 2**31 + 5]


@pytest.mark.parametrize("name", ["encode", "chat"])
def test_same_seed_same_requests(name):
    t = spec.load_cell({"encode": "bert_base.encode",
                        "chat": "glm4_9b.chat"}[name]).traffic
    for seed in SEEDS:
        a = list(itertools.islice(loadgen.client_stream(t, seed, 1, 1000), 25))
        b = list(itertools.islice(loadgen.client_stream(t, seed, 1, 1000), 25))
        assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
        assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_differ_in_order_not_in_sizes():
    t = spec.load_cell("glm4_9b.chat").traffic
    block = len(loadgen.prompt_lengths(t))
    mixes = []
    for seed in SEEDS:
        reqs = list(itertools.islice(loadgen.client_stream(t, seed, 0, 100),
                                     3 * block))
        mixes.append((Counter(len(r.prompt) for r in reqs),
                      Counter(r.max_new_tokens for r in reqs)))
        orders = [len(r.prompt) for r in reqs]
    assert all(m == mixes[0] for m in mixes)
    assert mixes[0][0] == Counter({512: 3, 1024: 6, 1536: 3})
    firsts = {tuple(len(r.prompt) for r in itertools.islice(
        loadgen.client_stream(t, s, 0, 100), block)) for s in SEEDS}
    assert len(firsts) > 1, orders


def test_output_lengths_are_log_uniform_quantiles():
    t = {"output_block": {"log_uniform": [64, 1024]}}
    outs = loadgen.output_lengths(t, 10)
    assert outs == sorted(outs) and outs[0] >= 64 and outs[-1] <= 1024
    ratios = np.diff(np.log(outs))
    assert np.allclose(ratios, ratios.mean(), atol=0.02)
    assert loadgen.output_lengths({"output_block": {"fixed": 1}}, 3) == [1] * 3


def test_warm_requests_cover_every_prompt_length_once():
    t = spec.load_cell("bert_base.encode").traffic
    warm = loadgen.warm_requests(t, 3, 30522)
    assert [len(w.prompt) for w in warm] == [64, 128, 256, 512]
    assert all(w.max_new_tokens == 1 for w in warm)


def test_tokens_stay_inside_the_vocabulary():
    t = spec.load_cell("bert_base.encode").traffic
    for r in itertools.islice(loadgen.client_stream(t, 9, 0, 50), 20):
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 50


def test_validate_refuses_a_request_longer_than_the_cache():
    t = dict(spec.load_cell("glm4_9b.chat").traffic, capacity=1024)
    with pytest.raises(ValueError, match="cache rows"):
        loadgen.validate(t)
    loadgen.validate(spec.load_cell("glm4_9b.chat").traffic)
