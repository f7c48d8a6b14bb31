"""Reduction of a profiler trace to numbers.

The trace is read into plain events (`Event`), so that the arithmetic can
be checked on a hand-made list: busy time is the UNION of the intervals
in which an operation ran on a device, an idle gap is the space between
two such intervals, and a gap is named by the host span (the benchmark's
own `TraceAnnotation`s) that covers most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def union_seconds(events: Iterable[Event], lo_ns: float = None,
                  hi_ns: float = None) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    spans = []
    for e in events:
        a = e.start_ns if lo_ns is None else max(e.start_ns, lo_ns)
        b = e.end_ns if hi_ns is None else min(e.end_ns, hi_ns)
        if b > a:
            spans.append((a, b))
    spans.sort()
    total, cur_a, cur_b = 0.0, None, None
    for a, b in spans:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total / 1e9


CONTAINERS = ("%while", "%cond", "%call")


def short_name(name: str) -> str:
    """'%leaf_histogram_blocklist.5 = f32[...] custom-call(...)' -> its
    instruction name."""
    return name.split(" = ", 1)[0][:120]


def operations(events: Iterable[Event]) -> List[Event]:
    """The 'XLA Ops' line also holds the loops and conditionals AROUND the
    operations (a while's event spans its whole body).  Those are left
    out: summed, every second would count at each level, and in a union
    they would hide every gap inside a dispatch."""
    return [e for e in events if not e.name.startswith(CONTAINERS)]


def top_ops(events: Iterable[Event], n: int = 10) -> List[List]:
    """[[name, seconds], ...] the operations that took most device time."""
    by_name: Dict[str, float] = {}
    for e in operations(events):
        k = short_name(e.name)
        by_name[k] = by_name.get(k, 0.0) + e.dur_ns / 1e9
    return [[k, v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(device: Sequence[Event], host_spans: Sequence[Event],
              lo_ns: float, hi_ns: float, n: int = 10) -> List[List]:
    """[[name, seconds], ...] idle time of the device inside [lo, hi],
    summed by the host span that covers most of each gap ("unnamed"
    where none does), the longest first."""
    spans = sorted((max(e.start_ns, lo_ns), min(e.end_ns, hi_ns))
                   for e in device if e.end_ns > lo_ns and e.start_ns < hi_ns)
    gaps, at = [], lo_ns
    for a, b in spans:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi_ns > at:
        gaps.append((at, hi_ns))
    by_name: Dict[str, float] = {}
    for a, b in gaps:
        name, cover = "unnamed", 0.0
        for s in host_spans:
            c = min(b, s.end_ns) - max(a, s.start_ns)
            if c > cover:
                name, cover = s.name, c
        by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def kernel_seconds(events: Iterable[Event], needles: Sequence[str]) -> float:
    """Summed device time of the events whose name holds any needle."""
    return sum(e.dur_ns for e in events
               if any(s in e.name for s in needles)) / 1e9


# -- reading the profiler's file -------------------------------------------
def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, host_names: Sequence[str]
                ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """-> ({device plane name: its operation events}, host spans with one
    of `host_names`).  Device planes are '/device:TPU:<n>'; their
    operations are the line 'XLA Ops' (every other line of such a plane
    repeats them at another level: steps, modules, names)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[plane.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        host.append(Event(e.name, e.start_ns, e.duration_ns))
    return device, host
