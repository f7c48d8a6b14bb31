"""What the program recorded of its own start, cut at the window.

The program keeps start-up spans (`lightgbm_tpu/utils/spans.py`
`startup_records()`: name, parent, t0, dur, stats), two stamps (`stamps()`:
the first dispatch, the first trees on the host) and a compile ledger
(`utils/compile_cache.py` `ledger()`: one record an executable, with its
trace, lowering, backend and cache-load seconds, `hit` and the context it
compiled in).  They are taken from the program in this process, as the
drivers take `dispatch_count()`, and kept where `t0` < the run's `setup_s`:
before the window.  `t0` is PROCESS AGE (the boot clock less the process's
start in /proc/self/stat, 10 ms steps) and `setup_s` runs from
`run.py`'s first lines, so the two clocks differ by the interpreter's own
start, some tens of milliseconds.  The names are this file's copy,
`scopes_startup.json` (`tests/test_spans.py` holds it equal to the
program's).  A program that keeps no such records (the parent of the PR
that added them) reads as None everywhere; nothing raises.

A traced run leaves ALL its records beside the trace
(`.bench_trace/startup_records.json`) for `phase_table_startup.py`.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDS_FILE = "startup_records.json"

with open(os.path.join(HERE, "scopes_startup.json")) as _fh:
    NAMES = json.load(_fh)

_left = False       # the records file was written by this process


def program_records() -> Optional[dict]:
    """{"spans", "ledger", "stamps"} as the program holds them now; None
    where it has none of them."""
    try:
        from lightgbm_tpu.utils import compile_cache, spans
        return {"spans": spans.startup_records(),
                "ledger": compile_cache.ledger(),
                "stamps": spans.stamps()}
    except (ImportError, AttributeError):
        return None


def leave_records(found: dict, setup_s: float, trace_dir: str) -> None:
    """All of the run's records, beside its trace."""
    if os.path.isdir(trace_dir):
        with open(os.path.join(trace_dir, RECORDS_FILE), "w") as fh:
            json.dump({"setup_s": setup_s, **found}, fh)


def before_window(record: dict) -> Optional[dict]:
    """The run's records whose `t0` lies before the window, or None.  A
    record may bring its own (`record["startup"]`: tests); a record
    without a process age (no /proc) is not before anything."""
    global _left
    found = record.get("startup") or program_records()
    setup_s = record.get("measures", {}).get("setup_s")
    if (not found or setup_s is None
            or found["stamps"].get("first_dispatch") is None):
        return None
    if record.get("trace") and "startup" not in record and not _left:
        _left = True
        leave_records(found, setup_s, os.path.join(ROOT, ".bench_trace"))

    def early(rs: List[dict]) -> List[dict]:
        return [r for r in rs if r["t0"] is not None and r["t0"] < setup_s]
    return {"spans": early(found["spans"]), "ledger": early(found["ledger"]),
            "first_dispatch": found["stamps"]["first_dispatch"]}


def span_seconds(record: dict, metric: str) -> Optional[float]:
    """Summed durations of the metric's start-up spans (`span_groups`)."""
    found = before_window(record)
    if found is None:
        return None
    names = NAMES["span_groups"][metric]
    return sum(r["dur"] for r in found["spans"] if r["name"] in names)


def first_dispatch(record: dict) -> Optional[float]:
    found = before_window(record)
    return None if found is None else found["first_dispatch"]


def unspanned(record: dict) -> Optional[float]:
    """The first dispatch's process age less the objective's and the
    booster's spans: what of the time before the device gets work lies
    under no start-up span.  Never negative."""
    at = first_dispatch(record)
    if at is None:
        return None
    return max(at - span_seconds(record, "startup_objective_s")
               - span_seconds(record, "startup_booster_s"), 0.0)


def ledger_seconds(record: dict, metric: str) -> Optional[float]:
    """Summed `fields` of the ledger's records (`ledger_groups`), of all
    of them or of those inside (`enqueue` true) or outside (false) an
    `lgbm.enqueue`."""
    found = before_window(record)
    if found is None:
        return None
    group = NAMES["ledger_groups"][metric]
    want = group["enqueue"]
    return sum(r[f] for r in found["ledger"] for f in group["fields"]
               if want is None
               or (r["context"] == NAMES["enqueue_context"]) == want)


def cache_misses(record: dict) -> Optional[int]:
    """Executables that compiled here before the window: ledger records
    whose `hit` is false (one that only traced or lowered has None)."""
    found = before_window(record)
    if found is None:
        return None
    return sum(1 for r in found["ledger"] if r["hit"] is False)


def self_seconds(spans: List[dict]) -> Dict[int, float]:
    """index -> the span's duration less its children's (the records
    whose parent is its name and whose start lies inside it)."""
    out = {}
    for i, r in enumerate(spans):
        inside = sum(c["dur"] for c in spans
                     if c["parent"] == r["name"] and c is not r
                     and None not in (c["t0"], r["t0"])
                     and r["t0"] <= c["t0"] <= r["t0"] + r["dur"])
        out[i] = r["dur"] - inside
    return out
