"""python sharded_worker.py <root> <cell> <mode> <seed>: one run of a tiny
sharded cell on four virtual CPU devices, in a process of its own (the
tests' process has one device); prints one JSON line.  `mode` is `sound`
(through `run_cell`, as the benchmark runs it), `control`, or a fault of
faults_sharded.py."""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("LGBM_TPU_NO_COMPILE_CACHE", "1")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH), HERE]


def main() -> int:
    root, name, mode, seed = sys.argv[1:5]
    import run as bench_run
    bench_run.keep_compile_cache = lambda root: None
    if mode == "sound":
        out = bench_run.run_cell(root, name, seed=int(seed), seconds=0.0,
                                 trace=False, require_tpu=False)
    else:
        import faults_sharded
        from harness.cells import Cell
        cell = Cell(root, name)
        control = mode == "control"
        record = cell.driver().run(
            cell, seed=int(seed), seconds=0.0, trace=False,
            t_process=time.time(), root=root, on_tpu=False, control=control,
            break_booster=None if control else faults_sharded.FAULTS[mode])
        out = {k: record[k] for k in ("correct", "compared", "numbers")}
        if control:
            out["control_correct"] = record["control_correct"]
            out["control_compared"] = record["control_compared"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
