"""What the bagged cell's per-layer metrics read of a traced run, grouped by
`scopes_bagged.json`.  `scopes.py` reads the trace but keeps only the host
spans `scopes.json` lists; the draw's span is read here, from the same file,
and nested with the others, so that `lgbm.host_inputs`' self time no longer
holds the draw it covers."""

from __future__ import annotations

import functools
import json
import os
from typing import Dict, List, Optional, Sequence

from harness import scopes, trace

with open(os.path.join(scopes.HERE, "scopes_bagged.json")) as _fh:
    NAMES = json.load(_fh)


def read_spans(path: str, names: Sequence[str]) -> List[scopes.Span]:
    """The host spans of the `.xplane.pb` that bear one of `names`, with
    their stats."""
    space = scopes._xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    found: List[scopes.Span] = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        wanted = {e.key: e.value.name for e in plane.event_metadata
                  if e.value.name in names}
        for line in plane.lines:
            found += [scopes.Span(wanted[e.metadata_id],
                                  line.timestamp_ns + e.offset_ps / 1e3,
                                  e.duration_ps / 1e3,
                                  scopes._stats(e.stats, stat_names),
                                  line.name)
                      for e in line.events if e.metadata_id in wanted]
    return found


@functools.lru_cache(maxsize=2)
def reduced(path: str) -> dict:
    """`scopes.reduce` of the trace with this file's host spans among the
    program's: nested with them, inside the window with them, and a name
    for the idle gaps they cover."""
    tr = scopes.read_trace(path)
    tr.host += read_spans(path, NAMES["host_spans"])
    red = scopes.reduce(tr)
    lo, hi = red["window_ns"]
    program = [s for s in red["spans"]
               if s.name in scopes.NAMES["host_spans"] + NAMES["host_spans"]]
    red["spans_in_window"] = [s for s in program
                              if s.start_ns >= lo and s.end_ns <= hi]
    first = next(iter(tr.device.values()), [])
    red["idle_by_span"] = trace.idle_gaps(
        trace.operations(first), sorted(program, key=lambda s: s.dur_ns),
        lo, hi)
    return red


def for_record(record: dict) -> Optional[dict]:
    """The reduction of the run's own trace; nothing for an untraced run."""
    if not record.get("trace"):
        return None
    try:
        path = trace.newest_xplane(os.path.join(scopes.ROOT, ".bench_trace"))
    except FileNotFoundError:
        return None
    return reduced(path)


def tree_seconds(record: dict, metric: str) -> Optional[float]:
    """A `*_tree_s` metric of the bagged cell: the seconds of the device
    scopes or host spans `scopes_bagged.json` groups under `metric`, over
    ALL the traced window's trees.  Nothing for an untraced run, for a trace
    with no `lgbm.*` scope (a device metric) or none of the program's spans
    (a host metric), and for `bag_draw_tree_s` where no draw is named."""
    red = for_record(record)
    trees = record.get("window_tree_count")
    if red is None or not trees:
        return None
    if metric in NAMES["device_groups"]:
        if not red["has_scopes"]:
            return None
        seconds = sum(red["device_s"].get(k, 0.0)
                      for k in NAMES["device_groups"][metric])
    else:
        group = NAMES["host_groups"][metric]
        spans = [s for s in red["spans_in_window"]
                 if s.name in group["spans"]]
        if not spans:
            return None
        seconds = sum((s.self_ns if group["time"] == "self" else s.dur_ns)
                      for s in spans) / 1e9
    return seconds / trees


def flush_counters(record: dict) -> Optional[Dict[str, int]]:
    """The sampling's counters, from the stats of the traced window's
    `lgbm.flush` spans: the first flush's window, bag and features (static),
    the draws summed over the flushes.  `scopes.py` reads a stat of value 0
    as absent (a protocol buffer leaves a default out), so a counter that
    is missing is 0, and nothing is returned where no flush carries any:
    a program without them, or a job that does not sample."""
    red = for_record(record)
    if red is None:
        return None
    flushes = [s.stats for s in red["spans_in_window"]
               if s.name == "lgbm.flush"]
    if not any(k in s for s in flushes for k in NAMES["flush_counters"]):
        return None
    out = {k: int(flushes[0].get(k, 0)) for k in NAMES["flush_counters"]}
    out["bag_draws"] = sum(int(s.get("bag_draws", 0)) for s in flushes)
    return out
