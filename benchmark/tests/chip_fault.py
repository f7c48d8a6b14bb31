"""python benchmark/tests/chip_fault.py --workload <cell> --fault <name> --seeds 1,2,3

Reads a planted fault, or the control, on the chip at the cell's own size:
one warm period and one timed period, with the program broken underneath
(faults.py) or, for `--fault control`, sound and the float8 control judged
in its place; `correct` as the cell's committed limits decide it, and the
numbers beside those limits.  Not run by the benchmark's own runs; its
readings are in PERF.md."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT, HERE]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    import jax
    import faults
    import run as bench_run
    from harness.cells import Cell
    bench_run.keep_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("chip_fault: no TPU")
    cell = Cell(ROOT, a.workload)
    for seed in (int(s) for s in a.seeds.split(",")):
        control = a.fault == "control"
        record = cell.driver().run(
            cell, seed=seed, seconds=0.0, trace=False, t_process=time.time(),
            root=ROOT, on_tpu=True, control=control,
            break_booster=None if control else faults.FAULTS[a.fault])
        print("chip_fault %s %s seed=%d correct=%s %s"
              % (a.workload, a.fault, seed, record["correct"],
                 json.dumps(record["compared"])), flush=True)
        if control:
            print("chip_fault %s control-in-place seed=%d correct=%s %s"
                  % (a.workload, seed, record["control_correct"],
                     json.dumps(record["control_compared"])), flush=True)
        print("chip_fault numbers %s" % json.dumps(record["numbers"]),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
