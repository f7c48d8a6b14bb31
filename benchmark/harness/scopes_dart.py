"""What the DART cell's per-layer metrics read of a traced run, grouped by
`scopes_dart.json`.  `scopes.py` reads the trace but keeps only the host
spans `scopes.json` lists; the lottery's span is read here, from the same
file (`scopes_bagged.read_spans`), and nested with the others, so that
`lgbm.host_inputs`' self time no longer holds the draws it covers."""

from __future__ import annotations

import functools
import json
import os
from typing import Dict, Optional

from harness import scopes, scopes_bagged, trace

with open(os.path.join(scopes.HERE, "scopes_dart.json")) as _fh:
    NAMES = json.load(_fh)


@functools.lru_cache(maxsize=2)
def reduced(path: str) -> dict:
    """`scopes.reduce` of the trace with this file's host spans among the
    program's: nested with them and inside the window with them."""
    tr = scopes.read_trace(path)
    tr.host += scopes_bagged.read_spans(path, NAMES["host_spans"])
    red = scopes.reduce(tr)
    lo, hi = red["window_ns"]
    names = scopes.NAMES["host_spans"] + NAMES["host_spans"]
    red["spans_in_window"] = [s for s in red["spans"] if s.name in names
                              and s.start_ns >= lo and s.end_ns <= hi]
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
    """A `*_tree_s` metric of the DART cell: the seconds of the device
    scopes or host spans `scopes_dart.json` puts under `metric` (a group, or
    a part of one), over ALL the traced window's trees.  Nothing for an
    untraced run, for a trace with no `lgbm.*` scope (a device metric) or
    none of the metric's spans (a host metric), and for a metric of DART's
    own scopes where the program names none of them."""
    red = for_record(record)
    trees = record.get("window_tree_count")
    if red is None or not trees:
        return None
    scoped = NAMES["device_groups"].get(metric,
                                        NAMES["device_parts"].get(metric))
    if scoped is not None:
        if not red["has_scopes"]:
            return None
        if (set(scoped) <= set(NAMES["device_scopes"])
                and not any(k in red["device_s"]
                            for k in NAMES["device_scopes"])):
            return None
        return sum(red["device_s"].get(k, 0.0) for k in scoped) / trees
    group = NAMES["host_groups"][metric]
    spans = [s for s in red["spans_in_window"] if s.name in group["spans"]]
    if not spans:
        return None
    return sum((s.self_ns if group["time"] == "self" else s.dur_ns)
               for s in spans) / 1e9 / trees


def flush_counters(record: dict) -> Optional[Dict[str, int]]:
    """DART's counters, from the stats of the traced window's `lgbm.flush`
    spans: the drops and the replays summed over the flushes, the bank's
    fill and bound as the last flush has them.  `scopes.py` reads a stat of
    value 0 as absent, so a missing counter is 0; nothing where no flush
    carries the bound: a program without them, or no DART job."""
    red = for_record(record)
    if red is None:
        return None
    flushes = [s.stats for s in red["spans_in_window"]
               if s.name == "lgbm.flush"]
    if not any("dart_bank_cap" in s for s in flushes):
        return None
    out = {k: sum(int(s.get(k, 0)) for s in flushes)
           for k in ("dart_drops", "dart_replayed")}
    out.update({k: int(flushes[-1].get(k, 0))
                for k in ("dart_bank_rows", "dart_bank_cap")})
    return out
