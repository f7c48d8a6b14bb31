"""What the class-wise cell's per-layer metrics read of a traced run, grouped
by `scopes_multi.json`; the trace itself is read by `scopes.py`."""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

from harness import scopes

with open(os.path.join(scopes.HERE, "scopes_multi.json")) as _fh:
    NAMES = json.load(_fh)


def group_seconds(red: dict, metric: str) -> float:
    """Device seconds of the scopes `scopes_multi.json` puts under `metric`
    (a group, or a part of one)."""
    scoped = NAMES["device_groups"].get(metric,
                                        NAMES["device_parts"].get(metric))
    return sum(red["device_s"].get(k, 0.0) for k in scoped)


def tree_seconds(record: dict, metric: str) -> Optional[float]:
    """A `*_tree_s` metric of the class-wise cell over ALL the traced
    window's trees.  Nothing for an untraced run or a trace with no
    `lgbm.*` scope.  (A program without the class key never gets this far:
    the cell's driver does not import on it.)"""
    red = scopes.for_record(record)
    trees = record.get("window_tree_count")
    if red is None or not trees or not red["has_scopes"]:
        return None
    return group_seconds(red, metric) / trees


def flush_counters(record: dict) -> Optional[Dict[str, int]]:
    """The class counters summed over the traced window's `lgbm.flush`
    spans; nothing where no flush carries them (a program without them, or
    a one-class job, whose zeros `scopes.py` reads as absent)."""
    red = scopes.for_record(record)
    if red is None:
        return None
    flushes = [s.stats for s in red["spans_in_window"]
               if s.name == "lgbm.flush" and "classes" in s.stats]
    if not flushes:
        return None
    return {k: sum(int(s.get(k, 0)) for s in flushes)
            for k in NAMES["flush_counters"]}
