"""python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell, on the machine it is started on.  Prints the result
as the last line of standard output; exits non-zero and prints no result
when JAX finds no TPU or fewer chips than the cell asks for, when
something compiles inside the measured window, or when the cell raises.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def device_block(devices, peak_bytes: int, trace: dict = None) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    if trace:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def keep_compile_cache(root: str) -> None:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program eligible."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True) -> dict:
    """The whole run but for the command line; -> the result object.
    `require_tpu=False` lifts the look for a chip, for tests only."""
    from harness.cells import Cell
    cell = Cell(root, workload)

    import jax
    keep_compile_cache(root)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise SystemExit("benchmark: cell %s needs %d TPU chip(s); JAX found "
                         "%d device(s) of platform %s"
                         % (workload, cell.chips, len(devices),
                            devices[0].platform))
    devices = devices[:cell.chips]

    record = cell.driver().run(cell, seed=seed, seconds=seconds, trace=trace,
                               t_process=T_PROCESS, root=root,
                               on_tpu=require_tpu)
    if trace:
        metrics = cell.per_layer(record)
    else:
        metrics = cell.end_to_end(record["measures"])
    result = {"correct": bool(record["correct"]),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]),
              "metrics": metrics,
              "device": device_block(devices, record["peak_bytes"],
                                     record.get("trace") if trace else None)}
    if trace and record.get("trace"):
        result["breakdown"] = record["trace"]["breakdown"]
    result["numbers"] = record["numbers"]
    result["checked_trees"] = record["checked_trees"]
    result["reference_s"] = record["reference_s"]
    result["compared"] = record["compared"]
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    result = run_cell(ROOT, a.workload, a.seed, a.seconds, bool(a.trace))
    sys.stdout.flush()
    for name, (value, limit) in result["compared"].items():
        print("compared %s = %r  limit %r" % (name, value, limit),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
