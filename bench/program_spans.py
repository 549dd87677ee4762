"""The program's own wall-clock spans (`npec.*`) in a JAX profiler trace,
reduced to where the host's time goes; and a command that prints that
reduction for one traced run of a cell:

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

It runs `bench/run.py` with `--trace 1` in this process, keeps the trace
that run reads, and after run.py's own output writes to standard error:
one row per span (count, total and self ms, the device busy ms of the
ops that start inside it), the same under `npec.engine.admit` and under
`npec.engine.decode`, the window's compile count, the `READINGS`, and
the longest idle gaps of the device labelled bench span / program span
/ PJRT event.  Standard output is run.py's, ending in its result line.

The program opens these spans (`repro.npec.obs.spans`); the benchmark
imports nothing of the program, so the names are spelled out here and a
test holds them to the program's constants.  `trace_reduce.read_xplane`
files them among `Trace.host`, where `split` finds them.  A trace
without them reduces to nothing, and its labels are `trace_reduce`'s.

Spans nest on the engine's host thread: a span's parent is the
innermost span that encloses it, and its self time is its length less
that of its `npec.*` children.  A device op belongs to the innermost
span open when it starts, which says what the host was doing then, not
which span enqueued it.
"""
from __future__ import annotations

import bisect
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402
from trace_reduce import Event, Trace  # noqa: E402

PREFIX = "npec."
ADMIT = "npec.engine.admit"
DECODE = "npec.engine.decode"
SYNC = "npec.engine.sync"
LOAD_SLOT = "npec.session.load_slot"
EXECUTE = "npec.exec.execute"
QUANTIZE_WEIGHT = "npec.exec.quantize_weight"
EXEC_PREFIX = "npec.exec."
CLASSES = ("param", "mmu", "attention", "nvu", "cache", "route", "feed",
           "tensor")
PARAM = EXEC_PREFIX + "param"
ROOTS = (ADMIT, DECODE)
COMPILE = "backend_compile_and_load"      # JAX's own span, once a compile


@dataclass
class Totals:
    count: Dict[str, int] = field(default_factory=dict)
    total_ns: Dict[str, float] = field(default_factory=dict)
    self_ns: Dict[str, float] = field(default_factory=dict)

    def add(self, name: str, total: float, own: float) -> None:
        self.count[name] = self.count.get(name, 0) + 1
        self.total_ns[name] = self.total_ns.get(name, 0.0) + total
        self.self_ns[name] = self.self_ns.get(name, 0.0) + own


@dataclass
class Program:
    """The `npec.*` spans that start in the traced window."""
    spans: Totals
    under: Dict[str, Totals]       # root -> the spans nested under one
    device_ns: Dict[str, float]    # innermost span -> busy ns of its ops
    compiles: int                  # compiles that started in the window

    def mean_ms(self, name: str, under: Optional[str] = None
                ) -> Optional[float]:
        t = self.spans if under is None else self.under[under]
        if not t.count.get(name):
            return None
        return t.total_ns[name] / t.count[name] * 1e-6

    def share(self, names: Sequence[str], root: str) -> Optional[float]:
        """Percent of the `root` spans' time spent in `names` under them."""
        whole = self.spans.total_ns.get(root, 0.0)
        if whole <= 0:
            return None
        part = sum(self.under[root].total_ns.get(n, 0.0) for n in names)
        return 100.0 * part / whole


# What a benchmark PR would make per-layer metrics of, by its proposed
# name: each gives None where the trace holds no such span.
READINGS: Dict[str, Callable[[Program], Optional[float]]] = {
    "prefill_exec_ms": lambda p: p.mean_ms(EXECUTE, under=ADMIT),
    "load_slot_ms": lambda p: p.mean_ms(LOAD_SLOT),
    "first_token_sync_ms": lambda p: p.mean_ms(SYNC, under=ADMIT),
    "weight_prep_share.prefill":
        lambda p: p.share((PARAM, QUANTIZE_WEIGHT), ADMIT),
    "weight_prep_share.decode":
        lambda p: p.share((PARAM, QUANTIZE_WEIGHT), DECODE),
    "decode_sync_ms": lambda p: p.mean_ms(SYNC, under=DECODE),
}


def split(host: List[Event]) -> Tuple[List[Event], List[Event]]:
    """(the program's `npec.*` spans, every other host event)."""
    prog = [e for e in host if e.name.startswith(PREFIX)]
    return prog, [e for e in host if not e.name.startswith(PREFIX)]


def window(tr: Trace) -> Tuple[float, float]:
    """`trace_reduce.summarize`'s window: the first bench step span's
    start to the last one's end; the whole trace where there is none."""
    steps = [s for s in tr.spans
             if s.name.startswith(trace_reduce.STEP_PREFIX)]
    if not steps:
        return float("-inf"), float("inf")
    return min(s.start for s in steps), max(s.end for s in steps)


def _parents(spans: List[Event]) -> List[Optional[int]]:
    """Index of each span's innermost enclosing span (`spans` sorted by
    start, the longer first at a tie)."""
    parent: List[Optional[int]] = []
    stack: List[int] = []
    for i, e in enumerate(spans):
        while stack and spans[stack[-1]].end <= e.start:
            stack.pop()
        parent.append(stack[-1] if stack else None)
        stack.append(i)
    return parent


def reduce(tr: Trace) -> Optional[Program]:
    """The program's spans over the traced window; None without any."""
    lo, hi = window(tr)
    prog, other = split(tr.host)
    spans = sorted((e for e in prog if lo <= e.start < hi),
                   key=lambda e: (e.start, -e.end))
    if not spans:
        return None
    parent = _parents(spans)
    child_ns = [0.0] * len(spans)
    for i, p in enumerate(parent):
        if p is not None:
            child_ns[p] += spans[i].end - spans[i].start
    root: List[Optional[str]] = []         # the nearest enclosing root
    for i, p in enumerate(parent):
        if p is None:
            root.append(None)
        else:
            up = spans[p].name
            root.append(up if up in ROOTS else root[p])
    every, under = Totals(), {r: Totals() for r in ROOTS}
    for i, e in enumerate(spans):
        total = e.end - e.start
        every.add(e.name, total, total - child_ns[i])
        if root[i] is not None:
            under[root[i]].add(e.name, total, total - child_ns[i])

    starts = [e.start for e in spans]
    planes = list(tr.device_ops.values())
    owned: Dict[str, list] = defaultdict(list)
    for ops in planes:
        for op in ops:
            if not lo <= op.start < hi:
                continue
            # the last span to start before the op, or the innermost of
            # its enclosing spans still open then
            i = bisect.bisect_right(starts, op.start) - 1
            owner = i if i >= 0 else None
            while owner is not None and spans[owner].end <= op.start:
                owner = parent[owner]
            if owner is not None:
                owned[spans[owner].name].append(op)
    device = {name: trace_reduce.busy_ns(ops, lo, hi) / len(planes)
              for name, ops in owned.items()}
    compiles = sum(1 for e in other
                   if e.name == COMPILE and lo <= e.start < hi)
    return Program(every, under, device, compiles)


def label(spans: List[Event], prog: List[Event], other: List[Event],
          t: float) -> str:
    """`trace_reduce._label` over the `other` host events, with the
    innermost program span covering `t` put between the bench span and
    the other host event."""
    base = trace_reduce._label(spans, other, t)
    cover = [e for e in prog if e.start <= t < e.end]
    if not cover:
        return base
    inner = min(cover, key=lambda e: e.end - e.start).name
    head, _, tail = base.partition(" / ")
    return " / ".join(p for p in (head, inner, tail) if p)


def top_gaps(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """`trace_reduce.summarize`'s longest idle gaps, relabelled."""
    if not tr.device_ops:
        return []
    lo, hi = window(tr)
    ops = next(iter(tr.device_ops.values()))
    gaps = sorted(trace_reduce.idle_gaps(ops, lo, hi),
                  key=lambda g: g[0] - g[1])
    prog, other = split(tr.host)
    return [(label(tr.spans, prog, other, (a + b) / 2), (b - a) * 1e-9)
            for a, b in gaps[:top]]


def report(tr: Trace) -> List[str]:
    """The lines the command writes for trace `tr`."""
    p = reduce(tr)
    if p is None:
        return ["program spans: none in the traced window"]
    ms = 1e-6
    lines = [f"program spans: {p.compiles} compiles in the window",
             "-- op class: count host_self_ms device_ms"]
    for name in [EXEC_PREFIX + c for c in CLASSES] + [QUANTIZE_WEIGHT]:
        if name in p.spans.count:
            lines.append(f"{name} {p.spans.count[name]} "
                         f"{p.spans.self_ns[name] * ms:.3f} "
                         f"{p.device_ns.get(name, 0.0) * ms:.3f}")
    lines.append("-- all: span count total_ms self_ms device_ms")
    t = p.spans
    for name in sorted(t.count, key=lambda n: -t.self_ns[n]):
        lines.append(f"{name} {t.count[name]} {t.total_ns[name] * ms:.3f} "
                     f"{t.self_ns[name] * ms:.3f} "
                     f"{p.device_ns.get(name, 0.0) * ms:.3f}")
    for r in ROOTS:
        t = p.under[r]
        lines.append(f"-- under {r}: span count total_ms self_ms")
        for name in sorted(t.count, key=lambda n: -t.self_ns[n]):
            lines.append(f"{name} {t.count[name]} "
                         f"{t.total_ns[name] * ms:.3f} "
                         f"{t.self_ns[name] * ms:.3f}")
    for name, read in READINGS.items():
        v = read(p)
        lines.append(f"reading {name} "
                     + ("none" if v is None else f"{v:.6g}"))
    for where, s in top_gaps(tr):
        lines.append(f"idle gap {s * 1e3:.3f} ms: {where}")
    return lines


def main(argv: Optional[Sequence[str]] = None) -> int:
    import run

    kept: List[Trace] = []
    read = trace_reduce.read_xplane

    def read_and_keep(path: str) -> Trace:
        kept.append(read(path))
        return kept[-1]

    trace_reduce.read_xplane = read_and_keep
    try:
        rc = run.main([*(sys.argv[1:] if argv is None else argv),
                       "--trace", "1"])
    finally:
        trace_reduce.read_xplane = read
    for tr in kept:
        for line in report(tr):
            run.log(line)
    return rc


if __name__ == "__main__":
    sys.exit(main())
