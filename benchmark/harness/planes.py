"""A traced run's device planes, one by one.

`scopes.reduce` averages the chips; a sharded step is read chip by chip:
a collective's time on a chip holds its wait for the slowest shard, and
the chips' sweeps differ by what their rows hold.  This module reads the
same `.xplane.pb` through `scopes.read_trace` and gives, for each
'/device:TPU:<n>' plane, its device seconds by the program's scope and
its busy seconds in the traced window, and the time of the collective
operations inside and outside the exchange's scope.  The arithmetic
works on `scopes.Trace`, so it is checked on a made trace with four
planes (`tests/test_planes.py`).
"""

from __future__ import annotations

import functools
import json
import os
import re
from typing import Dict, Optional

from harness import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "interconnect.json")) as _fh:
    INTERCONNECT = json.load(_fh)
EXCHANGE = "lgbm.hist_exchange"
# the histogram scopes a shard works through alone
OWN_HIST = [s for s in scopes.NAMES["device_groups"]["hist_tree_s"]
            if s != EXCHANGE]
# HLO instructions that cross chips, by OPCODE: an event is named by its
# instruction's whole text, '%psum.31 = f32[39,241,3]{...} all-reduce(...)',
# and the instruction's own name comes from the JAX primitive (psum, pmax)
COLLECTIVE = re.compile(r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast)"
                        r"(-start|-done)?\(")


def reduce(tr: scopes.Trace) -> dict:
    lo, hi = scopes.window(tr)
    own = [s for s in tr.host if s.name in scopes.NAMES["window_spans"]]
    planes: Dict[str, dict] = {}
    inside = outside = 0.0
    for name, ops in sorted(tr.device.items()):
        planes[name] = {
            "device_s": scopes.device_seconds(ops, lo, hi),
            "busy_s": trace.union_seconds(trace.operations(ops), lo, hi)}
        for e in trace.operations(ops):
            seconds = (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
            if seconds > 0 and COLLECTIVE.search(e.name):
                if scopes.scope_of(e.op_name) == EXCHANGE:
                    inside += seconds
                else:
                    outside += seconds
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    idlest = min(planes, key=lambda p: planes[p]["busy_s"])
    ops = trace.operations(tr.device[idlest])
    return {"window_s": (hi - lo) / 1e9, "planes": planes,
            "idlest": idlest,
            "has_scopes": any(k != scopes.UNSCOPED for p in planes.values()
                              for k in p["device_s"]),
            "collective_s": {"in_exchange_scope": inside,
                             "outside": outside},
            "device_ops": trace.top_ops(ops),
            "idle_gaps": trace.idle_gaps(ops, own, lo, hi)}


@functools.lru_cache(maxsize=2)
def reduced(path: str) -> dict:
    """One parse a process, whatever the number of readers."""
    return reduce(scopes.read_trace(path))


def for_record(record: dict) -> Optional[dict]:
    """The plane-by-plane reduction of the run's own trace; nothing for an
    untraced run, or where the program names no scope."""
    if not record.get("trace"):
        return None
    try:
        path = trace.newest_xplane(os.path.join(scopes.ROOT, ".bench_trace"))
    except FileNotFoundError:
        return None
    red = reduced(path)
    return red if red["has_scopes"] else None


def group_seconds(red: dict, names) -> Dict[str, float]:
    """{plane: summed device seconds of the scopes `names`}."""
    return {plane: sum(p["device_s"].get(k, 0.0) for k in names)
            for plane, p in red["planes"].items()}


def exchange_seconds(record: dict) -> Optional[float]:
    """Device seconds under the exchange's scope on the chip where they
    are most; nothing where no chip has any."""
    red = for_record(record)
    if red is None:
        return None
    most = max(group_seconds(red, [EXCHANGE]).values(), default=0.0)
    return most or None


def exchange_least_seconds(record: dict) -> float:
    """The least time one chip's links need for the window's all-reduces.
    A tree reduces one [F, B, 3] float32 histogram a leaf (the root's and
    each split's smaller child's).  Over S chips a chip sends, and
    receives, 2 (S - 1) / S of each (reduce-scatter, then all-gather):
    that many bytes at the chip's published interconnect rate."""
    leaves = sum(len(t["leaf_value"]) for t in record["window_trees"])
    s = record["shards"]
    wire = (2.0 * (s - 1) / s * leaves * record["features"]
            * record["hist_bins"] * 3 * 4)
    return wire / INTERCONNECT[record["device_kind"]]["bytes_per_s"]
