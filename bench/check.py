"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it served (drawn
from the seed, the longest always in it) is run through the
configuration's plain float32 reference, teacher-forced: one pass over
each prompt followed by its served tokens.  At every position where a
token was served, the gap is the reference's best logit minus the
reference's logit of the served token: 0 where the program chose the
reference's token, small where rounding made it choose a near tie, large
where it computed something else.  The widest gap is compared with the
configuration's limit.

The control (`gaps(..., control_bits=4)`) is the same reference with every weight
product rounded to int4, the precision below the int8 the configuration
states: at the same positions it reads the gap of the token that the
int4 model puts first.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

SAMPLE_STREAM = 1 << 21
# Requests compared in one run: at today's step times every request a
# run serves fits under it, so the check reads every served token.
SAMPLE_REQUESTS = 64
CONTROL_BITS = 4


@dataclass
class Served:
    """A prompt and the tokens the engine served for it, in order."""
    prompt: np.ndarray
    tokens: List[int]


def sample(items: Sequence[Served], k: int, seed: int) -> List[Served]:
    """At most `k` of `items`, drawn from the seed; the longest is always
    among them."""
    items = list(items)
    if len(items) <= k:
        return items
    longest = max(range(len(items)),
                  key=lambda i: len(items[i].prompt) + len(items[i].tokens))
    rest = [i for i in range(len(items)) if i != longest]
    rng = np.random.default_rng([seed % (1 << 64), SAMPLE_STREAM])
    pick = rng.choice(rest, size=k - 1, replace=False)
    return [items[longest]] + [items[i] for i in sorted(pick)]


def _rows_bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


def _logits(ref, config: Dict[str, Any], params, item: Served, pad: int,
            bits: Optional[int]) -> np.ndarray:
    """Reference logits at the positions that predicted each served token."""
    s, n = len(item.prompt), len(item.tokens)
    seq = np.concatenate([item.prompt, np.asarray(item.tokens[:-1],
                                                  np.int32)])
    if len(seq) > pad:
        raise ValueError(f"a served request of {len(seq)} tokens exceeds "
                         f"the reference's {pad} rows")
    tokens = np.zeros(pad, np.int32)
    tokens[:len(seq)] = seq
    rows = np.full(_rows_bucket(n), s - 1, np.int32)
    rows[:n] = np.arange(s - 1, s - 1 + n)
    out = ref.forward(config, params, tokens, rows, bits=bits)
    return np.asarray(out, np.float64)[:n]


def gaps(ref, config, params, items: Sequence[Served], pad: int,
         control_bits: Optional[int] = None):
    """Per served token, the best reference logit minus the served token's
    (the program's gaps); with `control_bits`, also per position the best
    reference logit minus that of the token the `control_bits`-bit
    reference puts first (the control's gaps, else None)."""
    prog, ctrl = [], []
    for item in items:
        lg = _logits(ref, config, params, item, pad, None)
        at = np.arange(len(item.tokens))
        best = lg.max(-1)
        tok = np.asarray(item.tokens, np.int64)
        valid = (tok >= 0) & (tok < lg.shape[-1])
        prog.append(np.where(valid, best - lg[at, np.where(valid, tok, 0)],
                             np.inf))
        if control_bits is not None:
            low = _logits(ref, config, params, item, pad, control_bits)
            ctrl.append(best - lg[at, low.argmax(-1)])
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0))
    return cat(prog), (cat(ctrl) if control_bits is not None else None)


@dataclass
class Verdict:
    correct: bool
    max_gap: float
    limit: float
    tokens: int
    requests: int
    reason: str = ""

    def checks(self) -> Dict[str, Dict[str, Optional[float]]]:
        """Strict JSON: a gap that is not a number (no token compared, or
        a token outside the vocabulary) reads as null."""
        value = self.max_gap if np.isfinite(self.max_gap) else None
        return {"max_logit_gap": {"value": value, "limit": self.limit}}


def judge(gaps: np.ndarray, limit: float, requests: int) -> Verdict:
    if gaps.size == 0:
        return Verdict(False, float("nan"), limit, 0, requests,
                       "no served token to compare")
    worst = float(gaps.max())
    ok = bool(np.isfinite(worst) and worst <= limit)
    return Verdict(ok, worst, limit, int(gaps.size), requests,
                   "" if ok else f"widest logit gap {worst} > {limit}")
