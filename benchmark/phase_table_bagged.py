"""python benchmark/phase_table_bagged.py <trace dir or .xplane.pb> [--ops N]

`phase_table.py`'s table of a job that samples rows and features, and under
it what that table cannot show: device seconds by the bagged cell's own
grouping (`harness/scopes_bagged.json`: the arrangement apart from the
re-sort, the out-of-bag descent apart from the partition), the draw's host
spans (`lgbm.bag_draw`, which `scopes.json` does not list) with the self time
of the spans around them, the device's idle gaps named with the draw among
the spans, and every stat the flushes and the arrangements carry.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import phase_table  # noqa: E402
from harness import scopes, scopes_bagged  # noqa: E402


def table(path: str) -> str:
    red = scopes_bagged.reduced(path)
    inside = red["spans_in_window"]
    trees = sum(s.stats.get("k", 0) for s in inside
                if s.name == "lgbm.segment") or 1
    names = scopes_bagged.NAMES
    out = ["", "%-26s %10s %10s" % ("bagged cell's metric", "seconds",
                                    "s/tree")]
    for metric, group in names["device_groups"].items():
        s = sum(red["device_s"].get(k, 0.0) for k in group)
        out.append("%-26s %10.3f %10.4f" % (metric, s, s / trees))
    out += ["", "%-26s %6s %10s %10s" % ("host span (in window)", "count",
                                         "seconds", "self s")]
    for name in sorted({s.name for s in inside}):
        group = [s for s in inside if s.name == name]
        out.append("%-26s %6d %10.4f %10.4f"
                   % (name, len(group), sum(s.dur_ns for s in group) / 1e9,
                      sum(s.self_ns for s in group) / 1e9))
    out += ["", "%-26s %10s" % ("idle gaps under", "seconds")]
    out += ["%-26s %10.4f" % (k, s) for k, s in red["idle_by_span"]]
    out += ["", "stats of each draw, arrangement and flush in the window"]
    for s in sorted(inside, key=lambda s: s.start_ns):
        if (s.name in ("lgbm.bag_draw", "lgbm.flush")
                or s.stats.get("kind") == "arrange"):
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
