"""Reduction of a profiler trace by the names the PROGRAM gives its phases.

The program wraps the phases of a tree in `jax.named_scope`s (`lgbm.*`,
which end up in every device operation's name stack) and its segment
loop's host work in `TraceAnnotation`s of the same family, with counts as
stats.  The names are listed in `scopes.json`, the benchmark's own copy.
This module reads the trace once and gives

  (a) device seconds by scope: every operation of a device's `XLA Ops`
      line that `trace.operations` keeps, clipped to the traced window,
      under the LAST `lgbm.*` component of its name stack, else under
      `unscoped`.  A fused operation that spans scopes counts under its
      root's: the known error of the method, bounded by the unscoped
      share and by the sum's distance from the trace's busy time;
  (b) the program's host spans with their stats, each one's parent (the
      span that covers it on its thread) and self time (its duration
      minus what its children cover);
  (c) the device's idle gaps by the program's host span that covers most
      of each, the innermost where several cover it alike.

The arithmetic works on plain records (`Op`, `Span`), so it is checked on
hand-made lists and on a sample recorded on the chip
(`tests/test_scopes.py`).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "scopes.json")) as _fh:
    NAMES = json.load(_fh)
UNSCOPED = NAMES["unscoped"]
_SCOPE = re.compile(r"lgbm\.[a-z_]+")


@dataclasses.dataclass(frozen=True)
class Op(trace.Event):
    """A device operation and the name stack its instruction carries."""
    op_name: str = ""


@dataclasses.dataclass
class Span:
    """A host span of the program (or one of the benchmark's own)."""
    name: str
    start_ns: float
    dur_ns: float
    stats: dict
    thread: str = ""
    parent: Optional[int] = None        # index in the list it came in
    self_ns: float = 0.0

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device: Dict[str, List[Op]]         # plane -> its 'XLA Ops' events
    host: List[Span]                    # lgbm.* and the window's spans


def scope_of(op_name: str) -> str:
    """The last `lgbm.*` component of a name stack, whatever loops,
    conditionals and inner jits stand between the scopes."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


def window(tr: Trace) -> Tuple[float, float]:
    """The traced window as the driver takes it: the extent of the
    benchmark's own host spans where the trace has them, else the first
    to the last device operation."""
    own = [s for s in tr.host if s.name in NAMES["window_spans"]]
    if own:
        return (min(s.start_ns for s in own), max(s.end_ns for s in own))
    ops = [e for evs in tr.device.values() for e in trace.operations(evs)]
    if not ops:
        raise RuntimeError("the trace holds no device operation")
    return (min(e.start_ns for e in ops), max(e.end_ns for e in ops))


def device_seconds(ops: Sequence[Op], lo_ns: float, hi_ns: float,
                   by_operation: bool = False) -> Dict:
    """{scope: seconds} of one device's operations inside [lo, hi], or
    {(instruction name, scope): seconds} `by_operation`."""
    out: Dict = {}
    for e in trace.operations(ops):
        d = min(e.end_ns, hi_ns) - max(e.start_ns, lo_ns)
        if d > 0:
            k = scope_of(e.op_name)
            if by_operation:
                k = (trace.short_name(e.name), k)
            out[k] = out.get(k, 0.0) + d / 1e9
    return out


def nest(spans: Sequence[Span]) -> List[Span]:
    """Fills `parent` and `self_ns`: on each thread a span's parent is
    the innermost span that covers it."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].thread, spans[i].start_ns,
                                  -spans[i].dur_ns))
    stack: List[int] = []
    for i in order:
        s = spans[i]
        s.parent, s.self_ns = None, s.dur_ns
        while stack and (spans[stack[-1]].thread != s.thread
                         or spans[stack[-1]].end_ns < s.end_ns):
            stack.pop()
        if stack:
            s.parent = stack[-1]
            spans[s.parent].self_ns -= s.dur_ns
        stack.append(i)
    return list(spans)


def idle_by_span(ops: Sequence[Op], spans: Sequence[Span], lo_ns: float,
                 hi_ns: float) -> List[List]:
    """[[span name, seconds], ...] idle time of one device by the
    program's host span covering most of each gap; shortest spans first,
    so the innermost wins where several cover a gap alike."""
    program = sorted((s for s in spans if s.name in NAMES["host_spans"]),
                     key=lambda s: s.dur_ns)
    return trace.idle_gaps(trace.operations(ops), program, lo_ns, hi_ns)


def reduce(tr: Trace) -> dict:
    """Everything the metrics and the table need, from one trace."""
    lo, hi = window(tr)
    planes = [device_seconds(ops, lo, hi) for ops in tr.device.values()]
    by_scope = {k: sum(p.get(k, 0.0) for p in planes) / len(planes)
                for k in set().union(*planes)}
    spans = nest(tr.host)
    inside = [s for s in spans if s.name in NAMES["host_spans"]
              and s.start_ns >= lo and s.end_ns <= hi]
    first = next(iter(tr.device.values()), [])
    return {
        "window_ns": (lo, hi),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(trace.union_seconds(trace.operations(ops), lo, hi)
                      for ops in tr.device.values()) / max(len(planes), 1),
        "device_s": by_scope,
        "has_scopes": any(k != UNSCOPED for k in by_scope),
        "spans": spans,
        "spans_in_window": inside,
        "idle_by_span": idle_by_span(first, spans, lo, hi),
    }


# -- the numbers the per-layer metrics report ------------------------------
def device_group_seconds(red: dict, metric: str) -> Optional[float]:
    """Summed device seconds of the scopes `scopes.json` groups under
    `metric`; nothing where the trace has no `lgbm.*` scope at all (a
    program that predates them), never 0."""
    if not red["has_scopes"]:
        return None
    return sum(red["device_s"].get(k, 0.0)
               for k in NAMES["device_groups"][metric])


def unscoped_pct(red: dict) -> Optional[float]:
    if not red["has_scopes"]:
        return None
    total = sum(red["device_s"].values())
    return 100.0 * red["device_s"].get(UNSCOPED, 0.0) / total


def host_group_seconds(red: dict, metric: str) -> Optional[float]:
    """Summed duration or self time (as `scopes.json` says) of the
    metric's host spans inside the window; nothing where the trace has
    none of the program's spans."""
    if not red["spans_in_window"]:
        return None
    group = NAMES["host_groups"][metric]
    return sum((s.self_ns if group["time"] == "self" else s.dur_ns)
               for s in red["spans_in_window"]
               if s.name in group["spans"]) / 1e9


def tree_seconds(record: dict, metric: str) -> Optional[float]:
    """A `*_tree_s` metric of a traced run: the group's seconds over ALL
    the window's trees (so a phase that runs once a period reads its
    amortised cost)."""
    red = for_record(record)
    trees = record.get("window_tree_count")
    if red is None or not trees:
        return None
    group = (device_group_seconds if metric in NAMES["device_groups"]
             else host_group_seconds)
    seconds = group(red, metric)
    return None if seconds is None else seconds / trees


# -- reading the profiler's file -------------------------------------------
# `jax.profiler.ProfileData` gives an event its name, its times and its OWN
# stats.  A TPU operation's name stack is a stat of the event's METADATA
# (`tf_op`, looked at on a v5e trace under jax 0.9.0: PERF.md section 6,
# PR 25), which ProfileData does not show, and two executables may hold
# instructions of one name.  So the file is read as what it is, an XSpace
# protocol buffer (tsl/profiler/protobuf/xplane.proto), with the few
# messages described here at run time: no generated module, no tensorflow.
OP_NAME_STAT = "tf_op"
_XPLANE_SCHEMA = {      # message: [(field, number, type, repeated)]
    "XStat": [("metadata_id", 1, "int64", 0), ("double_value", 2, "double", 0),
              ("uint64_value", 3, "uint64", 0), ("int64_value", 4, "int64", 0),
              ("str_value", 5, "string", 0), ("bytes_value", 6, "bytes", 0),
              ("ref_value", 7, "uint64", 0)],
    "XEvent": [("metadata_id", 1, "int64", 0), ("offset_ps", 2, "int64", 0),
               ("duration_ps", 3, "int64", 0), ("stats", 4, "XStat", 1)],
    "XLine": [("name", 2, "string", 0), ("timestamp_ns", 3, "int64", 0),
              ("events", 4, "XEvent", 1)],
    "XEventMetadata": [("name", 2, "string", 0), ("stats", 5, "XStat", 1)],
    "XStatMetadata": [("name", 2, "string", 0)],
    # a map field is a repeated entry message: key = 1, value = 2
    "EventMetadataEntry": [("key", 1, "int64", 0),
                           ("value", 2, "XEventMetadata", 0)],
    "StatMetadataEntry": [("key", 1, "int64", 0),
                          ("value", 2, "XStatMetadata", 0)],
    "XPlane": [("name", 2, "string", 0), ("lines", 3, "XLine", 1),
               ("event_metadata", 4, "EventMetadataEntry", 1),
               ("stat_metadata", 5, "StatMetadataEntry", 1)],
    "XSpace": [("planes", 1, "XPlane", 1)],
}


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    field = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="lgbm_bench_xplane.proto", package="lgbm_bench", syntax="proto3")
    for message, fields in _XPLANE_SCHEMA.items():
        m = fd.message_type.add(name=message)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=(field.LABEL_REPEATED if repeated
                                   else field.LABEL_OPTIONAL))
            if kind in _XPLANE_SCHEMA:
                f.type, f.type_name = field.TYPE_MESSAGE, ".lgbm_bench." + kind
            else:
                f.type = getattr(field, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("lgbm_bench.XSpace"))


def _stats(stats, stat_names: Dict[int, str]) -> dict:
    """{stat name: value} of an event or of its metadata; a value given
    by reference is the referred stat's name."""
    out = {}
    for st in stats:
        for f, v in st.ListFields():
            if f.name == "ref_value":
                v = stat_names.get(v, "")
            if f.name != "metadata_id":
                out[stat_names.get(st.metadata_id, "")] = v
    return out


def read_trace(path: str) -> Trace:
    """The `.xplane.pb` -> device operations with their name stacks and
    the host's `lgbm.*` spans (and the benchmark's own) with stats."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    keep = set(NAMES["host_spans"]) | set(NAMES["window_spans"])
    device: Dict[str, List[Op]] = {}
    host: List[Span] = []
    for plane in space.planes:
        on_device = plane.name.startswith("/device:TPU:")
        if not on_device and not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        for line in plane.lines:
            if on_device and line.name == "XLA Ops":
                op_names = {k: _stats(m.stats, stat_names).get(OP_NAME_STAT,
                                                               "")
                            for k, m in meta.items()}
                device[plane.name] = [
                    Op(meta[e.metadata_id].name,
                       line.timestamp_ns + e.offset_ps / 1e3,
                       e.duration_ps / 1e3, op_names[e.metadata_id])
                    for e in line.events]
            elif not on_device:
                host += [Span(meta[e.metadata_id].name,
                              line.timestamp_ns + e.offset_ps / 1e3,
                              e.duration_ps / 1e3,
                              _stats(e.stats, stat_names), line.name)
                         for e in line.events
                         if meta[e.metadata_id].name in keep]
    return Trace(device, host)


def find_xplane(path: str) -> str:
    """A `.xplane.pb`, or the newest one under a `jax.profiler.trace`
    directory."""
    return path if os.path.isfile(path) else trace.newest_xplane(path)


@functools.lru_cache(maxsize=2)
def reduced(path: str) -> dict:
    """One parse a process: nine readers ask for the same file."""
    return reduce(read_trace(path))


def for_record(record: dict) -> Optional[dict]:
    """The reduction of the run's own trace (`<root>/.bench_trace`, where
    the driver writes it); nothing for an untraced run."""
    if not record.get("trace"):
        return None
    try:
        path = trace.newest_xplane(os.path.join(ROOT, ".bench_trace"))
    except FileNotFoundError:
        return None
    return reduced(path)
