"""From a JAX profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

  * device ops: the events of each device plane's "XLA Ops" line; busy
    time is the union of their intervals, so overlapping ops count once;
  * host spans: the benchmark's own `TraceAnnotation`s (`bench.*`), on the
    host plane, on the same clock as the device events;
  * the traced window runs from the first `bench.step.*` span's start to
    the last one's end;
  * idle gaps are the stretches of the window in which no op ran on the
    device, each labelled by what the host was doing at its midpoint.

Everything below `read_xplane` works on plain `Event` lists, so the
arithmetic is tested without a trace file.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

STEP_PREFIX = "bench.step."
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns
    end: float              # ns


@dataclass
class Trace:
    device_ops: Dict[str, List[Event]]        # device plane -> its ops
    spans: List[Event]                        # bench.* host spans
    host: List[Event] = field(default_factory=list)   # other host events


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {logdir}, found {files}")
    return files[0]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
            if ops:          # planes without an op line are not chips
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = Event(e.name, e.start_ns, e.end_ns)
                    (spans if e.name.startswith(SPAN_PREFIX)
                     else host).append(ev)
    return Trace(device_ops, spans, host)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(events: List[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merge(clip(events, lo, hi)))


def idle_gaps(events: List[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for a, b in merge(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _label(spans: List[Event], host: List[Event], t: float) -> str:
    """The innermost bench span covering `t`, and the innermost other host
    event covering it."""
    def innermost(evs):
        cover = [e for e in evs if e.start <= t < e.end]
        return min(cover, key=lambda e: e.end - e.start).name if cover else None
    parts = [innermost(spans) or "outside bench spans", innermost(host)]
    return " / ".join(p for p in parts if p)


@dataclass
class Summary:
    window_ns: float
    busy_ns: float                  # averaged over the device planes
    ops: int                        # device ops starting in the window
    span_count: Dict[str, int]
    span_ns: Dict[str, float]
    span_ops: Dict[str, int]        # device ops starting inside the spans
    top_ops: List[Tuple[str, float]]          # (name, seconds)
    top_gaps: List[Tuple[str, float]]         # (label, seconds)


def summarize(tr: Trace, top: int = 10) -> Optional[Summary]:
    """None when the trace has no step span or no device."""
    steps = [s for s in tr.spans if s.name.startswith(STEP_PREFIX)]
    if not steps or not tr.device_ops:
        return None
    lo = min(s.start for s in steps)
    hi = max(s.end for s in steps)
    planes = list(tr.device_ops.values())
    busy = sum(busy_ns(ops, lo, hi) for ops in planes) / len(planes)
    all_ops = [e for ops in planes for e in ops if lo <= e.start < hi]
    starts = sorted(e.start for e in all_ops)

    import bisect
    count: Dict[str, int] = defaultdict(int)
    dur: Dict[str, float] = defaultdict(float)
    inside: Dict[str, int] = defaultdict(int)
    for s in steps:
        count[s.name] += 1
        dur[s.name] += s.end - s.start
        inside[s.name] += (bisect.bisect_left(starts, s.end)
                           - bisect.bisect_left(starts, s.start))
    by_name: Dict[str, float] = defaultdict(float)
    for a, b, name in ((max(e.start, lo), min(e.end, hi), e.name)
                       for e in all_ops):
        by_name[name] += (b - a) * 1e-9
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(planes[0], lo, hi), key=lambda g: g[0] - g[1])
    top_gaps = [(_label(tr.spans, tr.host, (a + b) / 2), (b - a) * 1e-9)
                for a, b in gaps[:top]]
    return Summary(window_ns=hi - lo, busy_ns=busy, ops=len(all_ops),
                   span_count=dict(count), span_ns=dict(dur),
                   span_ops=dict(inside), top_ops=top_ops,
                   top_gaps=top_gaps)
