"""The one load generator: request streams from a traffic file and a seed.

A traffic file (`bench/traffic/<name>.json`) holds only parameters:

  clients         number of closed-loop clients: each sends its next
                  request as its previous one finishes, with no think time
  slots, capacity the engine's decode slots and cache rows per slot
  prompt_block    {length: count}: every block of requests a client sends
                  holds exactly these prompt lengths, in an order drawn
                  from the seed, so every seed serves the same mix of sizes
  output_block    {"fixed": n} or {"log_uniform": [lo, hi]}: the new tokens
                  of the block's requests, log-uniform as the block's
                  quantiles (so again the same sizes for every seed)
  open_busy       whether set-up serves every client's first request, so
                  that the window opens with every slot busy

Prompt tokens are uniform over the configuration's vocabulary.  The same
seed gives the same requests, in the same order, on every run.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

WARM_STREAM = 1 << 20          # stream id of the set-up requests


@dataclass(frozen=True)
class RequestSpec:
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *stream])


def prompt_lengths(traffic: Dict) -> List[int]:
    """The block's prompt lengths, ascending, each as often as its count."""
    out: List[int] = []
    for length, count in sorted(traffic["prompt_block"].items(),
                                key=lambda kv: int(kv[0])):
        out += [int(length)] * int(count)
    return out


def output_lengths(traffic: Dict, n: int) -> List[int]:
    """`n` new-token counts: fixed, or the n quantiles of a log-uniform."""
    spec = traffic["output_block"]
    if "fixed" in spec:
        return [int(spec["fixed"])] * n
    lo, hi = spec["log_uniform"]
    a, b = math.log(lo), math.log(hi)
    return [int(round(math.exp(a + (i + 0.5) / n * (b - a))))
            for i in range(n)]


def validate(traffic: Dict) -> None:
    lengths = prompt_lengths(traffic)
    longest = max(lengths) + max(output_lengths(traffic, len(lengths))) - 1
    if longest > traffic["capacity"]:
        raise ValueError(
            f"the longest request needs {longest} cache rows; the traffic "
            f"gives the engine {traffic['capacity']}")


def client_stream(traffic: Dict, seed: int, client: int,
                  vocab: int) -> Iterator[RequestSpec]:
    """Client `client`'s requests, endless: block k is a seeded permutation
    of the block's prompt lengths paired with a seeded permutation of its
    output lengths."""
    lengths = prompt_lengths(traffic)
    outs = output_lengths(traffic, len(lengths))
    k = 0
    while True:
        rng = _rng(seed, client, k)
        for s, n in zip(rng.permutation(lengths), rng.permutation(outs)):
            yield RequestSpec(rng.integers(0, vocab, int(s), dtype=np.int32),
                              int(n))
        k += 1


def warm_requests(traffic: Dict, seed: int, vocab: int) -> List[RequestSpec]:
    """One one-token request for each prompt length of the mix: set-up
    runs them so that every prefill shape is compiled before the window."""
    rng = _rng(seed, WARM_STREAM)
    return [RequestSpec(rng.integers(0, vocab, s, dtype=np.int32), 1)
            for s in sorted(set(prompt_lengths(traffic)))]
