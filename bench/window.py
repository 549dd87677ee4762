"""The measured window: closed-loop clients driving the engine, and the
arithmetic from the host timeline to the end-to-end metrics.

The engine is driven only through its public calls (`submit`, `step`,
`stats`, `pool`).  Every time is the host's `perf_counter`, read when a
call returns; `step()` ends in a host sync (it turns the step's logits
into a NumPy array), so the time after it includes the device's work.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

from loadgen import RequestSpec


@dataclass
class Tracked:
    """One request as its client saw it."""
    client: int
    spec: RequestSpec
    send_t: float
    req: Any                               # the engine's Request
    token_t: List[float] = field(default_factory=list)
    done: bool = False


@dataclass
class Step:
    t0: float
    t1: float
    kind: str                              # "admit" or "decode"
    prefills: int                          # engine prefills in this step
    tokens: int                            # tokens emitted in this step


@dataclass
class Timeline:
    t_open: float
    t_close: float = 0.0
    requests: List[Tracked] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)


def _no_annotation(name: str):
    return contextlib.nullcontext()


class ClosedLoop:
    """`clients` closed-loop clients over one engine: a client sends its
    next request, with no think time, as soon as its previous one is
    done."""

    def __init__(self, engine, streams: List[Iterator[RequestSpec]],
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: Callable = _no_annotation):
        self.engine = engine
        self.streams = streams
        self.clock = clock
        self.annotate = annotate
        self.live: Dict[int, Tracked] = {}        # client -> its request
        self.all: List[Tracked] = []

    def _send(self, client: int) -> None:
        spec = next(self.streams[client])
        t = self.clock()
        with self.annotate("bench.client.submit"):
            req = self.engine.submit(spec.prompt,
                                     max_new_tokens=spec.max_new_tokens)
        tr = Tracked(client, spec, t, req)
        self.live[client] = tr
        self.all.append(tr)

    def send_idle(self) -> None:
        for c in range(len(self.streams)):
            if c not in self.live:
                self._send(c)

    def step(self) -> Step:
        eng = self.engine
        waiting = any(not tr.token_t for tr in self.live.values())
        kind = "admit" if waiting and eng.pool.free_ids() else "decode"
        before = eng.stats.prefills
        t0 = self.clock()
        with self.annotate(f"bench.step.{kind}"):
            eng.step()
        t1 = self.clock()
        tokens = 0
        for c, tr in list(self.live.items()):
            new = len(tr.req.generated) - len(tr.token_t)
            tr.token_t += [t1] * new
            tokens += new
            if tr.req.done:
                tr.done = True
                del self.live[c]
        return Step(t0, t1, kind, eng.stats.prefills - before, tokens)

    def settle(self) -> None:
        """Set-up: send every client's first request and step until each
        has its first token, so the window opens with every slot busy."""
        self.send_idle()
        while any(not tr.token_t for tr in self.live.values()):
            self.step()

    def run(self, seconds: float) -> Timeline:
        """Whole engine steps until `seconds` have passed since the open."""
        tl = Timeline(t_open=self.clock())
        carried = list(self.all)
        while True:
            self.send_idle()
            st = self.step()
            tl.steps.append(st)
            if st.t1 - tl.t_open >= seconds:
                break
        tl.t_close = tl.steps[-1].t1
        seen = {id(tr) for tr in carried}
        tl.requests = ([tr for tr in carried if not _done_before(tr, tl)]
                       + [tr for tr in self.all if id(tr) not in seen])
        return tl


def _done_before(tr: Tracked, tl: Timeline) -> bool:
    return tr.done and tr.token_t[-1] <= tl.t_open


def warm(engine, specs: List[RequestSpec]) -> List[Any]:
    """Set-up: serve each spec to its end (one per prompt length), so that
    every prefill shape the window can meet is compiled.  Returns the
    engine's requests, which the correctness check reads too."""
    reqs = [engine.submit(spec.prompt, max_new_tokens=spec.max_new_tokens)
            for spec in specs]
    while engine.step():
        pass
    return reqs


def percentile(samples: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(samples, np.float64), q))


def summarize(tl: Timeline) -> Dict[str, float]:
    """End-to-end numbers of one window.

    * TTFT samples: every request sent in the window, from its send to
      its first token; one with no first token by the close counts at
      close - send (censored, so that a slower system cannot look better
      by starting fewer requests).
    * ITL samples: every gap between successive tokens of one request
      whose later token came in the window.
    * output tokens/s: all tokens emitted in the window over the elapsed
      time of its whole steps.
    """
    elapsed = tl.t_close - tl.t_open
    ttft, itl, tokens = [], [], 0
    for tr in tl.requests:
        if tr.send_t >= tl.t_open:
            first = tr.token_t[0] if tr.token_t else tl.t_close
            ttft.append(first - tr.send_t)
        for a, b in zip(tr.token_t, tr.token_t[1:]):
            if tl.t_open < b <= tl.t_close:
                itl.append(b - a)
        tokens += sum(1 for t in tr.token_t if tl.t_open < t <= tl.t_close)
    out: Dict[str, float] = {
        "window_s": elapsed, "steps": len(tl.steps), "output_tokens": tokens,
        "ttft_samples": len(ttft), "itl_samples": len(itl),
        "censored": sum(1 for tr in tl.requests
                        if tr.send_t >= tl.t_open and not tr.token_t),
        "output_tokens_per_s": tokens / elapsed if elapsed > 0 else 0.0,
    }
    if ttft:
        out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    if itl:
        out["itl_p50_ms"] = 1e3 * percentile(itl, 50)
        out["itl_p95_ms"] = 1e3 * percentile(itl, 95)
    return out


def served(tl: Timeline) -> List[Tracked]:
    """The window's requests that were served at least one token."""
    return [tr for tr in tl.requests if tr.token_t]


def step_kind_errors(tl: Timeline) -> Optional[str]:
    """A step labelled decode-only that ran a prefill, or the reverse."""
    bad = [s for s in tl.steps if (s.prefills > 0) != (s.kind == "admit")]
    return f"{len(bad)} steps mislabelled" if bad else None
