"""Tiny cells for the CPU tests: the two configurations cut to a few
dozen lanes, same code paths, with traffic to match.  A test changes the
tiny dicts, never the committed files."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

TINY_WIDTHS = {
    "bert_base": (
        {"hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
         "intermediate_size": 128, "vocab_size": 512,
         "max_position_embeddings": 128},
        {"d_model": 64, "num_layers": 4, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512,
         "max_position": 128}),
    "glm4_9b": (
        {"hidden_size": 64, "num_layers": 2, "num_attention_heads": 4,
         "multi_query_group_num": 2, "kv_channels": 16,
         "ffn_hidden_size": 96, "padded_vocab_size": 512},
        {"d_model": 64, "num_layers": 2, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 96, "vocab_size": 512}),
}

TINY_TRAFFIC = {
    "encode": {"capacity": 64, "prompt_block": {"8": 2, "16": 1}},
    "chat": {"capacity": 64, "clients": 3, "slots": 3,
             "prompt_block": {"8": 2, "16": 1},
             "output_block": {"log_uniform": [4, 24]}},
}


def tiny_cell(name: str, limit: float = None) -> spec.Cell:
    """Workload `name` of BENCHMARK.json at tiny widths."""
    cell = spec.load_cell(name)
    c = copy.deepcopy(cell.config)
    published, program = TINY_WIDTHS[cell.config_name]
    c.update(published)
    c["program"]["overrides"] = dict(program)
    if limit is not None:
        c["check"]["max_logit_gap"] = limit
    t = copy.deepcopy(cell.traffic)
    t.update(TINY_TRAFFIC[cell.traffic_name])
    cell.config, cell.traffic = c, t
    return cell


class Ticks:
    """A clock that moves 0.1 s at every reading, so that a window holds
    the same steps however busy the machine running the test is."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 0.1
        return self.t
