"""Driver of kind `train_dart`: `train.py`'s steady boosting on a booster
that boosts with dropouts (`boosting_type=dart`, `drop_rate`, `drop_seed` in
the configuration's parameters).

What differs from `train.py`.  The window is FIXED: the traffic file's
`window_periods` whole periods after the warm one (a traced run times the
first), whatever `--seconds` says: a DART tree's cost depends on its index
(the lottery drops a tenth of the trees there are, the leaf bank fills a row
a tree), so two runs are compared only over the same trees.  After the window
the driver keeps the program's drop lists (`DART.drop_history()`), and what
the window produced goes with them to `harness/reference_dart.py`, which
checks them against upstream's stream and reads the comparison under
dropping.  The loop, the warm period and the record are `train.py`'s, by
import where a function stands alone there.
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
from harness import reference, reference_dart, trace as trace_mod
from harness.data import make_rows
# The cell needs a program whose DART job fits the chip and says what it
# drew: one without (a leaf bank of num_iterations x rows bytes, 34 GB here)
# fails at this import, before it asks the device for anything.
from lightgbm_tpu.utils.spans import DART_DRAW  # noqa: F401


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
    period = int(params["hist_reorder_every"])
    window_periods = 1 if trace else int(cell.traffic["window_periods"])
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
    warm_trees = WARM_PERIODS * period
    drive(booster, warm_trees, TraceAnnotation)
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
        with TraceAnnotation("sync"):
            jax.block_until_ready(booster.scores)
        p1 = time.perf_counter()
        window_asked += period
        periods.append((p0, p1, done))
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
    scores = np.asarray(booster._training_score(), np.float32).reshape(-1)
    drops = booster.drop_history()
    bank = booster._dart_counters()
    produced = reference.Produced(trees=trees, scores=scores,
                                  trees_asked=warm_trees + window_asked)
    window_trees = trees[warm_trees:]
    del booster, flush
    gc.collect()

    # one tree from each executable of the window's last period: the last
    # tree of its first dispatch (the re-sort's) and of the last dispatch of
    # each size, the window's last tree among them
    ends = len(trees) - sum(sizes) + np.cumsum(sizes) - 1
    checked = sorted({int(ends[0]), *(int(e) for e in
                                      dict(zip(sizes, ends)).values())})
    t_ref = time.perf_counter()
    numbers = reference_dart.compare(
        rows.bins, rows.label, params, produced,
        [t for t in checked if t >= 0], drops, control)
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
        "drops": [len(d) for d in drops],
        "bank_rows": bank["dart_bank_rows"], "bank_cap": bank["dart_bank_cap"],
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
