"""Reduction of a profiler trace to device intervals and host spans.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. A device plane (``/device:TPU:<i>``) has a line of XLA
operations and a line of XLA modules (one event per run of a compiled
program); the host plane has one line per thread, on which the
benchmark's ``TraceAnnotation`` spans appear by name. All timestamps are
nanoseconds on one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    """One traced window: per device, its operations and module runs; the
    benchmark's host spans; and the window itself."""
    ops: List[List[Event]]
    modules: List[List[Event]]
    spans: List[Event]
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(root: str) -> str:
    hits = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return hits[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str, span_names: Iterable[str], window_span: str) -> Trace:
    """Read ``path`` (an ``.xplane.pb``) and keep the device lines, the
    host events named in ``span_names`` and the ``window_span``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    keep = set(span_names) | {window_span}
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append(_events(lines["XLA Ops"]) if "XLA Ops" in lines else [])
            modules.append(_events(lines["XLA Modules"])
                           if "XLA Modules" in lines else [])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln) if e.name in keep)
    wins = [s for s in spans if s.name == window_span]
    if len(wins) != 1:
        raise ValueError(f"expected one {window_span!r} span, found {len(wins)}")
    w = wins[0]
    clip = lambda evs: [e for e in evs if e.end > w.start and e.start < w.end]
    return Trace([clip(o) for o in ops], [clip(m) for m in modules],
                 sorted((s for s in spans if s.name != window_span),
                        key=lambda s: s.start),
                 (w.start, w.end))


def merge(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """Union of the intervals, clipped to ``[lo, hi]``, in order."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(trace: Trace, device: int = 0) -> List[Interval]:
    """The intervals in which some operation ran on ``device``."""
    if device >= len(trace.ops):
        return []
    return merge(((e.start, e.end) for e in trace.ops[device]), *trace.window)


def busy_s(trace: Trace) -> float:
    """Busy seconds, averaged over the devices traced."""
    per = [sum(e - s for s, e in busy(trace, d)) / 1e9
           for d in range(len(trace.ops))]
    return sum(per) / len(per) if per else 0.0


def overlap(intervals: Sequence[Interval], lo: int, hi: int) -> int:
    """Nanoseconds of the (merged, ordered) ``intervals`` inside [lo, hi]."""
    i = max(bisect.bisect_right([s for s, _ in intervals], lo) - 1, 0)
    total = 0
    for s, e in intervals[i:]:
        if s >= hi:
            break
        total += max(0, min(e, hi) - max(s, lo))
    return total


def spans(trace: Trace, name: str) -> List[Event]:
    return [s for s in trace.spans if s.name == name]


def innermost(trace: Trace, t: int) -> Optional[str]:
    """Name of the shortest host span that holds instant ``t``."""
    best = None
    for s in trace.spans:
        if s.start <= t < s.end and (best is None
                                     or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best else None


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` device operations (by name) that took the most time."""
    tot: Dict[str, int] = {}
    for e in trace.ops[0] if trace.ops else []:
        lo, hi = max(e.start, trace.window[0]), min(e.end, trace.window[1])
        tot[e.name] = tot.get(e.name, 0) + max(0, hi - lo)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_span(trace: Trace, n: int = 10) -> List[List]:
    """Idle device time, gap by gap, summed by the host span each gap's
    midpoint fell in (``host`` where no span held it); the ``n`` largest."""
    lo, hi = trace.window
    edges = [lo] + [t for iv in busy(trace) for t in iv] + [hi]
    tot: Dict[str, int] = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            name = innermost(trace, (s + e) // 2) or "host"
            tot[name] = tot.get(name, 0) + (e - s)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
