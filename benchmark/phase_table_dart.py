"""python benchmark/phase_table_dart.py <trace dir or .xplane.pb> [--ops N]

`phase_table.py`'s table of a job that boosts with dropouts, and under it what
that table cannot show: device seconds by the DART cell's own grouping
(`harness/scopes_dart.json`: the drop, the normalise, a replayed drop and the
bank's append apart from the score update, the leaf bank's carry inside the
re-sort), the lottery's host spans (`lgbm.dart_draw`, which `scopes.json` does
not list) with the self time of the spans around them, and every stat the
re-sorting dispatches and the flushes carry.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import phase_table  # noqa: E402
from harness import scopes, scopes_dart  # noqa: E402


def table(path: str) -> str:
    red = scopes_dart.reduced(path)
    inside = red["spans_in_window"]
    trees = sum(s.stats.get("k", 0) for s in inside
                if s.name == "lgbm.segment") or 1
    names = scopes_dart.NAMES
    out = ["", "%-26s %10s %10s" % ("DART cell's metric", "seconds",
                                    "s/tree")]
    for kind in ("device_groups", "device_parts"):
        for metric, group in names[kind].items():
            s = sum(red["device_s"].get(k, 0.0) for k in group)
            out.append("%-26s %10.3f %10.4f%s" % (
                metric, s, s / trees,
                "  (a part of its group)" if kind == "device_parts" else ""))
    out += ["", "%-26s %6s %10s %10s" % ("host span (in window)", "count",
                                         "seconds", "self s")]
    for name in sorted({s.name for s in inside}):
        group = [s for s in inside if s.name == name]
        out.append("%-26s %6d %10.4f %10.4f"
                   % (name, len(group), sum(s.dur_ns for s in group) / 1e9,
                      sum(s.self_ns for s in group) / 1e9))
    out += ["", "the lottery, tree by tree (k), then the stats of each "
            "re-sorting dispatch and flush in the window"]
    out.append(" ".join(str(s.stats.get("k", 0)) for s in sorted(
        inside, key=lambda s: s.start_ns) if s.name == "lgbm.dart_draw"))
    for s in sorted(inside, key=lambda s: s.start_ns):
        if s.name == "lgbm.flush" or s.stats.get("kind") == "resort":
            out.append("%-14s %8.3f s  %s" % (
                s.name, s.dur_ns / 1e9,
                " ".join("%s=%s" % kv for kv in sorted(s.stats.items()))))
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--ops", type=int, default=20)
    a = ap.parse_args()
    path = scopes.find_xplane(a.trace)
    print(phase_table.table(scopes.read_trace(path), a.ops))
    print(table(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
