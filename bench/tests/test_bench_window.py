"""End-to-end arithmetic on hand-made timelines, and the closed loop on a
stand-in engine with a hand-driven clock."""
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from loadgen import RequestSpec
from window import ClosedLoop, Step, Timeline, Tracked, summarize

SPEC = RequestSpec(np.zeros(4, np.int32), 1)


def tracked(send, tokens):
    return Tracked(client=0, spec=SPEC, send_t=send, req=None,
                   token_t=list(tokens))


def test_censored_ttft_counts_the_wait_so_far():
    tl = Timeline(t_open=10.0, t_close=20.0, requests=[
        tracked(10.0, [12.0]),             # 2 s
        tracked(12.0, [16.0]),             # 4 s
        tracked(16.0, []),                 # still waiting: 20 - 16 = 4 s
    ], steps=[Step(10, 12, "admit", 1, 1), Step(12, 16, "admit", 1, 1),
              Step(16, 20, "decode", 0, 0)])
    e = summarize(tl)
    assert e["ttft_samples"] == 3 and e["censored"] == 1
    assert e["ttft_p50_ms"] == pytest.approx(4000.0)
    assert e["ttft_p95_ms"] == pytest.approx(4000.0)


def test_requests_sent_before_the_window_give_no_ttft():
    tl = Timeline(t_open=10.0, t_close=14.0,
                  requests=[tracked(5.0, [9.0, 11.0, 14.0])])
    e = summarize(tl)
    assert e["ttft_samples"] == 0 and "ttft_p50_ms" not in e
    # gaps whose later token falls in the window: 11-9 and 14-11
    assert e["itl_samples"] == 2
    assert e["output_tokens"] == 2
    assert e["output_tokens_per_s"] == pytest.approx(2 / 4.0)


def test_tail_is_over_all_samples_of_all_requests():
    slow = tracked(0.0, [1.0] + [1.0 + i for i in range(1, 21)])   # 1 s gaps
    fast = tracked(0.0, [1.0] + [1.0 + 0.1 * i for i in range(1, 201)])
    tl = Timeline(t_open=0.5, t_close=30.0, requests=[slow, fast])
    e = summarize(tl)
    gaps = [1.0] * 20 + [0.1] * 200
    assert e["itl_samples"] == 220
    assert e["itl_p95_ms"] == pytest.approx(1e3 * np.percentile(gaps, 95))
    assert e["output_tokens"] == 2 + 20 + 200


class FakeEngine:
    """Admits one queued request per free slot and emits one token per
    step for each slot, on a clock the test advances by `step_s`."""

    def __init__(self, clock, slots, step_s):
        self.clock, self.step_s = clock, step_s
        self.pool = SimpleNamespace(free_ids=self._free)
        self.stats = SimpleNamespace(prefills=0)
        self.queue, self.slots = [], [None] * slots

    def _free(self):
        return [i for i, r in enumerate(self.slots) if r is None]

    def submit(self, prompt, max_new_tokens):
        req = SimpleNamespace(generated=[], done=False, n=max_new_tokens)
        self.queue.append(req)
        return req

    def step(self):
        for i in self._free():
            if self.queue:
                self.slots[i] = self.queue.pop(0)
                self.stats.prefills += 1
        for i, r in enumerate(self.slots):
            if r is not None:
                r.generated.append(0)
                if len(r.generated) >= r.n:
                    r.done, self.slots[i] = True, None
        self.clock.t += self.step_s
        return True


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


def stream(n_new):
    while True:
        yield RequestSpec(np.zeros(3, np.int32), n_new)


def test_window_runs_whole_steps_past_the_seconds():
    clock = Clock()
    eng = FakeEngine(clock, slots=1, step_s=3.0)
    loop = ClosedLoop(eng, [stream(1)], clock=clock)
    tl = loop.run(10.0)
    assert len(tl.steps) == 4                  # 3, 6, 9, 12 >= 10
    assert tl.t_close - tl.t_open == pytest.approx(12.0)
    e = summarize(tl)
    assert e["ttft_samples"] == 4 and e["ttft_p50_ms"] == pytest.approx(3000)
    assert all(s.kind == "admit" and s.prefills == 1 for s in tl.steps)


def test_clients_beyond_the_slots_wait_and_are_censored():
    clock = Clock()
    eng = FakeEngine(clock, slots=1, step_s=1.0)
    loop = ClosedLoop(eng, [stream(5), stream(5)], clock=clock)
    tl = loop.run(3.0)
    e = summarize(tl)
    # client 1 waits behind client 0's five tokens for the whole window
    assert e["censored"] == 1
    assert e["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile([1.0, 3.0], 95))


def test_settle_opens_with_every_client_served_once():
    clock = Clock()
    eng = FakeEngine(clock, slots=2, step_s=1.0)
    loop = ClosedLoop(eng, [stream(4), stream(4)], clock=clock)
    loop.settle()
    assert all(tr.token_t for tr in loop.live.values())
    tl = loop.run(2.0)
    e = summarize(tl)
    assert e["ttft_samples"] == 0 and e["itl_samples"] == 4
    assert e["output_tokens_per_s"] == pytest.approx(2.0)
