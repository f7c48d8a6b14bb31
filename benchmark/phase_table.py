"""python benchmark/phase_table.py <trace dir or .xplane.pb> [--ops N] [--sample OUT.json]

The phases of a training job, from any JAX profiler trace of the program
(`jax.profiler.trace(dir)` around `lightgbm_tpu.train(...)`, or the
benchmark's own `.bench_trace`): device seconds by the program's
`lgbm.*` scope and by per-layer metric, the host's `lgbm.*` spans with
their counts and self time, the device's idle gaps by the span that
covers them, and the operations that took most time with the scope each
counts under.  The window is the benchmark's own (`dispatch`..`sync`)
where the trace has it, else the first to the last device operation;
trees are the `k` of the `lgbm.segment` spans inside it.

`--sample` also writes a slice of the trace as JSON (some hundred device
operations and every host span), the form `tests/test_scopes.py` reads.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import scopes  # noqa: E402


def _sum_stat(spans, key):
    values = [s.stats[key] for s in spans if isinstance(s.stats.get(key),
                                                        (int, float))]
    return sum(values) if values else None


def table(tr: scopes.Trace, n_ops: int) -> str:
    red = scopes.reduce(tr)
    inside = red["spans_in_window"]
    trees = _sum_stat([s for s in inside if s.name == "lgbm.segment"], "k")
    per_tree = (lambda s: "%10.4f" % (s / trees)) if trees else (
        lambda s: "%10s" % "-")
    total = sum(red["device_s"].values()) or 1.0
    out = ["window %.3f s, device busy %.3f s (idle %.3f%%), %s trees"
           % (red["window_s"], red["busy_s"],
              100.0 * (1.0 - red["busy_s"] / red["window_s"]),
              trees if trees else "unknown"),
           "", "%-24s %10s %7s %10s" % ("device scope", "seconds", "share",
                                        "s/tree")]
    for k, s in sorted(red["device_s"].items(), key=lambda kv: -kv[1]):
        out.append("%-24s %10.3f %6.2f%% %s"
                   % (k, s, 100.0 * s / total, per_tree(s)))
    out.append("%-24s %10.3f (sum of operations; busy is their union)"
               % ("all operations", total))
    out += ["", "%-24s %10s %10s" % ("per-layer metric", "seconds",
                                     "s/tree")]
    for metric in scopes.NAMES["device_groups"]:
        s = scopes.device_group_seconds(red, metric)
        out.append("%-24s %s" % (metric, "nothing to read" if s is None
                                 else "%10.3f %s" % (s, per_tree(s))))
    u = scopes.unscoped_pct(red)
    out.append("%-24s %s" % ("device_unscoped_pct", "nothing to read"
                             if u is None else "%9.3f%%" % u))
    for metric in scopes.NAMES["host_groups"]:
        s = scopes.host_group_seconds(red, metric)
        out.append("%-24s %s" % (metric, "nothing to read" if s is None
                                 else "%10.4f %s" % (s, per_tree(s))))
    out += ["", "%-24s %6s %10s %10s  %s" % ("host span (in window)",
                                             "count", "seconds", "self s",
                                             "stats summed")]
    keys = sorted({(s.name, s.stats.get("kind", "")) for s in inside})
    for name, kind in keys:
        group = [s for s in inside
                 if (s.name, s.stats.get("kind", "")) == (name, kind)]
        sums = {k: _sum_stat(group, k) for k in ("k", "trees", "bytes")}
        out.append("%-24s %6d %10.4f %10.4f  %s"
                   % (name + (" " + kind if kind else ""), len(group),
                      sum(s.dur_ns for s in group) / 1e9,
                      sum(s.self_ns for s in group) / 1e9,
                      " ".join("%s=%d" % kv for kv in sums.items()
                               if kv[1] is not None)))
    out += ["", "%-24s %10s" % ("idle gaps under", "seconds")]
    out += ["%-24s %10.4f" % (k, s) for k, s in red["idle_by_span"]]
    out += ["", "%-44s %-20s %9s" % ("operation (first device)", "scope",
                                     "seconds")]
    by_op = scopes.device_seconds(next(iter(tr.device.values()), []),
                                  *red["window_ns"], by_operation=True)
    for (name, scope), s in sorted(by_op.items(),
                                   key=lambda kv: -kv[1])[:n_ops]:
        out.append("%-44s %-20s %9.3f" % (name[:44], scope, s))
    return "\n".join(out)


def sample(tr: scopes.Trace, n: int) -> dict:
    """`n` consecutive operations from the middle of the first device's
    line and every host span, as JSON-ready lists."""
    plane, ops = next(iter(tr.device.items()))
    a = max(0, len(ops) // 2 - n // 2)
    return {"device": {plane: [[e.name[:160], e.start_ns, e.dur_ns,
                                e.op_name] for e in ops[a:a + n]]},
            "host": [[s.name, s.start_ns, s.dur_ns, s.stats, s.thread]
                     for s in tr.host]}


def from_sample(obj: dict) -> scopes.Trace:
    return scopes.Trace(
        {p: [scopes.Op(*e) for e in evs]
         for p, evs in obj["device"].items()},
        [scopes.Span(*s) for s in obj["host"]])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="a jax.profiler trace directory, or an "
                                  ".xplane.pb, or a --sample JSON")
    ap.add_argument("--ops", type=int, default=20)
    ap.add_argument("--sample", metavar="OUT.json")
    ap.add_argument("--sample-ops", type=int, default=400)
    a = ap.parse_args()
    if a.trace.endswith(".json"):
        with open(a.trace) as fh:
            tr = from_sample(json.load(fh))
    else:
        tr = scopes.read_trace(scopes.find_xplane(a.trace))
    print(table(tr, a.ops))
    if a.sample:
        with open(a.sample, "w") as fh:
            json.dump(sample(tr, a.sample_ops), fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
