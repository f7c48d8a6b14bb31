"""Driver of kind `train_multi`: `train.py`'s steady boosting on a class-wise
job (`objective=multiclass`, `num_class` K in the configuration's
parameters), whose every dispatch grows K trees, one a class, from one
softmax.

What differs from `train.py`.  A period is `hist_reorder_every`
ITERATIONS, K trees each (the program counts its re-sort cadence in
iterations).  The window is FIXED at the traffic file's `window_periods`
whole periods after the warm one, whatever `--seconds` says; a traced run
times the same window.  The program defers its flushes by 16 iterations,
longer than a window, so this module flushes at each period's end, inside
the window, where the other cells' flushes fall on their own.  The rows are
images (`harness/data_multi.py`), and the reference is
`harness/reference_multi.py`: it checks three classes' trees (the first, the
middle and the last) in each executable's iteration of the window, and
every class's final scores.  The loop, the warm period and the record are
`train.py`'s, by import where a function stands alone there.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from drivers.train import (HOST_SPANS, WARM_PERIODS, CompileMeter,
                           build_booster, drive, peak_bytes, reduce_trace,
                           tree_dict)
from harness import reference, reference_multi, trace as trace_mod
from harness.data_multi import make_rows
# The cell needs a program whose class-wise re-sort sorts a packed key: one
# without (K keys + an iota in one lax.sort, some seven minutes of cold
# compile at K = 10 for a described v5e, against 50 s) fails at this
# import, before it asks the device for anything.
from lightgbm_tpu.utils.spans import CLASS_KEY  # noqa: F401


def checked_classes(k: int):
    """The classes whose trees the reference regrows: the first, the
    middle and the last (0, 4 and 9 of ten)."""
    return sorted({0, (k - 1) // 2, k - 1})


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        root: str, on_tpu: bool, break_booster=None,
        control: bool = False) -> dict:
    """-> the run's record, `train.run`'s.  `break_booster(booster)` is for
    tests that plant a fault under the timed path; `control` has the
    reference also compute the float8 control and judge it in the program's
    place."""
    import jax
    from jax.profiler import TraceAnnotation
    from lightgbm_tpu.models.gbdt import dispatch_count

    config = cell.config
    params = config["params"]
    k = int(params["num_class"])
    period = int(params["hist_reorder_every"])      # iterations
    window_periods = int(cell.traffic["window_periods"])
    meter = CompileMeter()
    devices = jax.devices()[:cell.chips]

    rows = make_rows(config["data"], int(config["num_data"]),
                     int(params["max_bin"]), seed)
    booster = build_booster(config, rows, on_tpu)
    if break_booster is not None:
        break_booster(booster)
    flush = booster._flush_pending

    def flush_span():
        with TraceAnnotation("flush"):
            return flush()
    booster._flush_pending = flush_span

    # warm-up, on the booster that is then timed
    warm_iters = WARM_PERIODS * period
    drive(booster, warm_iters, TraceAnnotation)
    booster._flush_pending()
    jax.block_until_ready(booster.scores)
    setup_s = time.time() - t_process
    setup_compile_s, compiles_before = meter.seconds, meter.count

    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    d0 = dispatch_count()
    periods = []
    window_asked = 0
    t0 = time.perf_counter()
    for _ in range(window_periods):
        p0 = time.perf_counter()
        sizes = drive(booster, period, TraceAnnotation)
        done = sum(sizes)
        booster._flush_pending()
        with TraceAnnotation("sync"):
            jax.block_until_ready(booster.scores)
        p1 = time.perf_counter()
        window_asked += period * k
        periods.append((p0, p1, done * k))
        if done < period:
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    dispatches = dispatch_count() - d0
    if meter.count != compiles_before:
        raise RuntimeError(
            "%d backend compile(s) or cache load(s) inside the measured "
            "window: the warm-up did not cover it"
            % (meter.count - compiles_before))
    peak = peak_bytes(devices)

    # what the timed path produced
    trees = [tree_dict(t) for t in booster.models]
    scores = np.asarray(booster._training_score(), np.float32)   # [K, N]
    produced = reference.Produced(trees=trees, scores=scores,
                                  trees_asked=(warm_iters * k
                                               + window_asked))
    window_trees = trees[warm_iters * k:]
    del booster, flush
    gc.collect()

    # one iteration from each executable of the window's last period: its
    # first (the re-sort's) and its last; in each the checked classes
    iters = len(trees) // k
    last = sorted({iters - sum(sizes), iters - 1})
    checked = [it * k + c for it in last if it >= 0
               for c in checked_classes(k)]
    t_ref = time.perf_counter()
    numbers = reference_multi.compare(rows.bins, rows.label, params,
                                      produced, checked, control)
    correct, compared = reference.judge(numbers, cell.limits)

    record = {
        "correct": correct, "compared": compared, "numbers": numbers,
        "checked_trees": checked,
        "reference_s": time.perf_counter() - t_ref,
        "attempted": window_asked,
        "failed": window_asked - len(window_trees),
        "measures": {"train_tree_s": window_s / max(len(window_trees), 1),
                     "setup_s": setup_s},
        "window_trees": window_trees,
        "window_tree_count": len(window_trees),
        "periods": [(b - a, n) for a, b, n in periods],
        "dispatches": dispatches, "setup_compile_s": setup_compile_s,
        "peak_bytes": peak, "in_bag_rows": int(config["num_data"]),
        "features": int(rows.bins.shape[0]),
        "device_kind": devices[0].device_kind,
    }
    if control:
        record["control_correct"], record["control_compared"] = (
            reference.judge(reference.as_control(numbers), cell.limits))
    if trace:
        dev, host = trace_mod.read_xplane(trace_mod.newest_xplane(trace_dir),
                                          HOST_SPANS)
        record["trace"] = reduce_trace(dev, host)
    return record
