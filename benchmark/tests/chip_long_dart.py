"""python benchmark/tests/chip_long_dart.py --workload <cell> --trees 96 --seed <n>

One DART job of the cell's configuration on PAST its leaf bank's bound, on the
chip at the cell's own size: period by period (the device awaited at period
ends) the seconds a tree, the trees dropped and how many of them lay outside
the bank and were replayed, and the device's peak memory; the LAST period
under the profiler, with the DART cell's table of it
(`phase_table_dart.py`): what a replayed drop costs beside a banked one.
Not run by the benchmark's own runs; its readings are in PERF.md."""

import argparse
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT, HERE]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trees", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args()
    import jax
    from jax.profiler import TraceAnnotation
    import phase_table_dart
    import run as bench_run
    from drivers import train_dart
    from harness import scopes
    from harness.cells import Cell
    from harness.data import make_rows
    bench_run.keep_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("chip_long_dart: no TPU")
    cell = Cell(ROOT, a.workload)
    config, params = cell.config, cell.config["params"]
    period = int(params["hist_reorder_every"])
    rows = make_rows(config["data"], int(config["num_data"]),
                     int(params["max_bin"]), a.seed)
    booster = train_dart.build_booster(config, rows, on_tpu=True)
    cap = booster._bank_plan[0] - 1
    trace_dir = os.path.join(ROOT, ".bench_trace")
    periods = -(-a.trees // period)
    for p in range(periods):
        last = p == periods - 1
        if last:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        train_dart.drive(booster, period, TraceAnnotation)
        with TraceAnnotation("sync"):
            jax.block_until_ready(booster.scores)
        seconds = time.perf_counter() - t0
        if last:
            jax.profiler.stop_trace()
        drops = booster.drop_history()[p * period:(p + 1) * period]
        print("chip_long_dart trees %d-%d: %.4f s/tree, %d dropped, %d of "
              "them replayed (bank of %d), peak %.3f GiB"
              % (p * period, (p + 1) * period - 1, seconds / period,
                 sum(len(d) for d in drops),
                 sum(t >= cap for d in drops for t in d), cap,
                 train_dart.peak_bytes(jax.devices()[:1]) / 2.0 ** 30),
              flush=True)
    print(phase_table_dart.table(scopes.find_xplane(trace_dir)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
