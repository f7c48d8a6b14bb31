"""python benchmark/tests/chip_fault_multi.py --workload <cell> --plan control:1,hessian_halved:2,sound:3 [--budget S]

`chip_fault.py` for the class-wise cell (faults_multi.py:
`per_class_gradients`, `next_class_scores`, `scores_not_permuted`,
`hessian_halved`; `control`: sound, and the float8 control judged in its
place; `sound`: sound alone; `traced`: sound, with the cell's per-layer
metrics from a traced window), at the cell's own size on the chip, each on
the seed the plan gives it.  The plan's runs share ONE process, each after
the program's step cache is emptied and the functions a fault replaced are
put back, so that they share what compiled once; none starts once the
process has run `--budget` seconds.  Readings are in PERF.md."""

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
    ap.add_argument("--plan", required=True)
    ap.add_argument("--budget", type=float, default=float("inf"))
    a = ap.parse_args()
    t_start = time.time()
    import jax
    import faults_multi
    import run as bench_run
    from harness.cells import Cell
    from lightgbm_tpu.models import gbdt
    bench_run.keep_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("chip_fault_multi: no TPU")
    cell = Cell(ROOT, a.workload)
    real = {f: getattr(gbdt, f) for f in faults_multi.PATCHED}
    for step in a.plan.split(","):
        fault, seed = step.split(":")
        seed = int(seed)
        if time.time() - t_start > a.budget:
            print("chip_fault budget spent before %s" % step, flush=True)
            break
        control = fault == "control"
        gbdt._FUSED_STEPS.clear()
        try:
            record = cell.driver().run(
                cell, seed=seed, seconds=0.0, trace=fault == "traced",
                t_process=time.time(), root=ROOT, on_tpu=True,
                control=control,
                break_booster=(None if fault in ("control", "sound", "traced")
                               else faults_multi.FAULTS[fault]))
        finally:
            for f, fn in real.items():
                setattr(gbdt, f, fn)
        print("chip_fault %s %s seed=%d correct=%s %s"
              % (a.workload, fault, seed, record["correct"],
                 json.dumps(record["compared"])), flush=True)
        if control:
            print("chip_fault %s control-in-place seed=%d correct=%s %s"
                  % (a.workload, seed, record["control_correct"],
                     json.dumps(record["control_compared"])), flush=True)
        print("chip_fault numbers %s %s train_tree_s=%r reference_s=%r"
              % (fault, json.dumps(record["numbers"]),
                 record["measures"]["train_tree_s"],
                 record["reference_s"]), flush=True)
        if fault == "traced":
            print("chip_fault per_layer %s" % json.dumps(
                cell.per_layer(record)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
