"""python benchmark/phase_table_startup.py <trace dir | startup_records.json | .xplane.pb>

The start-up table of a training job: every start-up span the program kept
(`lightgbm_tpu/utils/spans.py` `startup()`) with its parent, start (process
age), duration, self time and stats, then every record of its compile
ledger (`utils/compile_cache.py`) by context with its four durations and
`hit`, and the sums the start-up metrics read.  From the records a traced
benchmark run leaves beside its trace (`.bench_trace/startup_records.json`,
`harness/startup.py`); records past the window's start are marked `*`.

Given a trace that HOLDS the start-up spans (a job traced from its first
line; the benchmark's own traces start at the window), it prints them from
the trace instead, on the device's clock, each with the device's busy
seconds inside it; the `lgbm.enqueue` spans that say `first=1` stand for
the first calls, which are records and no annotations.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import startup, trace as trace_mod  # noqa: E402

DURATIONS = ("trace_s", "lower_s", "backend_s", "retrieval_s")


def _stats(stats: dict) -> str:
    return " ".join("%s=%s" % (k, "%.3f" % v if isinstance(v, float) else v)
                    for k, v in sorted(stats.items()))


def _at(t0) -> str:
    return "%9s" % "?" if t0 is None else "%9.3f" % t0


def records_table(found: dict) -> str:
    setup_s = found["setup_s"]
    spans, ledger = found["spans"], found["ledger"]
    own = startup.self_seconds(spans)

    def late(r) -> str:
        return " " if r["t0"] is not None and r["t0"] < setup_s else "*"
    out = ["setup_s %.3f; first dispatch at %s s, first trees on the host at "
           "%s s (process age)" % (setup_s,
                                   _at(found["stamps"].get("first_dispatch")),
                                   _at(found["stamps"].get("first_tree"))),
           "", "%-24s %-22s %9s %9s %9s  %s"
           % ("start-up span", "parent", "t0 s", "seconds", "self s",
              "stats")]
    for i, r in enumerate(spans):
        out.append("%-24s %-22s %s %9.3f %9.3f %s%s"
                   % (r["name"], r["parent"] or "-", _at(r["t0"]), r["dur"],
                      own[i], late(r), _stats(r["stats"])))
    out += ["", "%-20s %-34s %9s %8s %8s %8s %8s  %s"
            % ("context", "executable", "t0 s", "trace", "lower", "backend",
               "load", "hit")]
    for r in sorted(ledger, key=lambda r: (r["context"], r["call"] or [],
                                           r["t0"] or 0.0)):
        where = r["context"]
        if r["call"]:
            where = "%s %s k=%s x%s" % (("enqueue",) + tuple(r["call"]))
        out.append("%-20s %-34s %s %8.3f %8.3f %8.3f %8.3f %s%s"
                   % (where, r["fun"][:34], _at(r["t0"]),
                      *(r[f] for f in DURATIONS), late(r),
                      {True: "hit", False: "MISS", None: "-"}[r["hit"]]))
    early = [r for r in ledger if late(r) == " "]
    by_context = {}
    for r in early:
        key = r["context"]
        by_context[key] = by_context.get(key, 0.0) + sum(r[f]
                                                         for f in DURATIONS)
    out += ["", "before the window: %d ledger records, %s; misses %d; by "
            "context %s"
            % (len(early),
               ", ".join("%s %.3f" % (f, sum(r[f] for r in early))
                         for f in DURATIONS),
               sum(1 for r in early if r["hit"] is False),
               ", ".join("%s %.3f" % kv for kv in sorted(by_context.items())))]
    return "\n".join(out)


def trace_table(path: str) -> str:
    """The start-up spans of a trace that holds them, with the device's
    busy seconds inside each (first device plane)."""
    from jax.profiler import ProfileData
    names = startup.NAMES["startup_spans"]
    device, _ = trace_mod.read_xplane(path, ())
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            found += [(e.start_ns, e.duration_ns, e.name, dict(e.stats))
                      for line in plane.lines for e in line.events
                      if e.name in names
                      or (e.name == startup.NAMES["enqueue_context"]
                          and dict(e.stats).get("first"))]
    if not found:
        return "no start-up span in %s" % path
    ops = (trace_mod.operations(next(iter(device.values())))
           if device else [])
    t_first = min(s[0] for s in found)
    out = ["%-24s %10s %9s %10s %9s  %s"
           % ("start-up span (trace)", "start s", "seconds", "dev busy s",
              "dev idle", "stats")]
    for start, dur, name, stats in sorted(found):
        busy = trace_mod.union_seconds(ops, start, start + dur)
        out.append("%-24s %10.3f %9.3f %10.3f %8.1f%%  %s"
                   % (name, (start - t_first) / 1e9, dur / 1e9, busy,
                      100.0 * (1.0 - busy / max(dur / 1e9, 1e-12)),
                      _stats(stats)))
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("source")
    a = ap.parse_args()
    records = (os.path.join(a.source, startup.RECORDS_FILE)
               if os.path.isdir(a.source) else a.source)
    if records.endswith(".json") and os.path.exists(records):
        with open(records) as fh:
            print(records_table(json.load(fh)))
        return 0
    path = (a.source if a.source.endswith(".pb")
            else trace_mod.newest_xplane(a.source))
    print(trace_table(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
