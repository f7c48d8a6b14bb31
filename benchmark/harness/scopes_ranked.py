"""What the ranking cells' per-layer metrics read of a traced run, grouped
by `scopes_ranked.json`; the trace itself is read by `scopes.py`."""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

from harness import scopes

with open(os.path.join(scopes.HERE, "scopes_ranked.json")) as _fh:
    NAMES = json.load(_fh)


def tree_seconds(record: dict, metric: str) -> Optional[float]:
    """Device seconds a tree of the traced window spent under the scopes
    `scopes_ranked.json` groups under `metric`.  Nothing for an untraced
    run, for a trace with no `lgbm.*` scope, and, for a metric of the
    `lgbm.rank_*` scopes, for a program that does not name them."""
    red = scopes.for_record(record)
    trees = record.get("window_tree_count")
    if red is None or not trees or not red["has_scopes"]:
        return None
    group = NAMES["device_groups"][metric]
    if (set(group) <= set(NAMES["device_scopes"])
            and not any(k in red["device_s"] for k in group)):
        return None
    return sum(red["device_s"].get(k, 0.0) for k in group) / trees


def flush_counters(record: dict) -> Optional[Dict[str, int]]:
    """The ranking objective's counters, from the stats of the traced
    window's `lgbm.flush` spans (each what ONE tree costs, so every flush
    says the same); nothing where no flush carries them."""
    red = scopes.for_record(record)
    if red is None:
        return None
    for s in red["spans_in_window"]:
        if s.name == "lgbm.flush" and all(
                k in s.stats for k in NAMES["flush_counters"]):
            return {k: int(s.stats[k]) for k in NAMES["flush_counters"]}
    return None
