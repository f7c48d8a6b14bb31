"""Driver of kind `train_sharded`: `train.py`'s steady boosting on a booster
whose rows are sharded over the cell's chips (`tree_learner=data`).

The booster is built and driven as `train.py` builds and drives it (its
helpers are imported, not copied): `create_objective`, `create_boosting`,
`device_type=tpu`, one warm period on the booster that is then timed,
whole periods through `GBDT.train_segment(remaining, is_eval=False)`, the
devices awaited at period ends only.  What differs is what four chips
force: the rows are made by a pool of threads (`harness/data_parallel.py`,
the same arrays), the reference works shard by shard on the chips the
program has freed (`harness/reference_sharded.py`), the peak memory is the
fullest chip's, and a traced run keeps the device planes apart
(`harness/planes.py`).
"""

from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import time

import numpy as np

from drivers.train import (WARM_PERIODS, CompileMeter, build_booster, drive,
                           peak_bytes, tree_dict)
from harness import planes, reference, reference_sharded, trace as trace_mod
from harness.data_parallel import make_rows


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        root: str, on_tpu: bool, break_booster=None,
        control: bool = False) -> dict:
    """-> the run's record, with `train.run`'s keys and the shards'.
    `break_booster(booster)` is for tests that plant a fault under the
    timed path; `control` has the reference also compute the float8
    control and judge it in the program's place."""
    import jax
    from jax.profiler import TraceAnnotation
    from lightgbm_tpu.models.gbdt import dispatch_count

    config = cell.config
    params = config["params"]
    period = int(params["hist_reorder_every"])
    shards = int(params["num_shards"])
    if shards != cell.chips:
        raise ValueError("cell %s: num_shards=%d on %d chip(s)"
                         % (cell.name, shards, cell.chips))
    meter = CompileMeter()
    devices = jax.devices()[:shards]

    clock = [("start", time.time())]
    rows = make_rows(config["data"], int(config["num_data"]),
                     int(params["max_bin"]), seed)
    clock.append(("rows", time.time()))
    booster = build_booster(config, rows, on_tpu)
    clock.append(("booster", time.time()))
    if break_booster is not None:
        break_booster(booster)
    flush = getattr(booster, "_flush_pending", None)
    if flush is not None:

        def flush_span():
            with TraceAnnotation("flush"):
                return flush()
        booster._flush_pending = flush_span

    # warm-up, on the booster that is then timed
    warm_trees = WARM_PERIODS * period
    drive(booster, warm_trees, TraceAnnotation)
    jax.block_until_ready(booster.scores)
    setup_s = time.time() - t_process
    clock.append(("warm", time.time()))
    setup_compile_s, compiles_before = meter.seconds, meter.count

    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)

    @contextlib.contextmanager
    def dispatch_span(name):
        """`drive`'s span around each dispatch; a dispatch of the window
        that compiled (or loaded from the cache) ends the run there."""
        with TraceAnnotation(name):
            yield
        if meter.count != compiles_before:
            raise RuntimeError(
                "%d backend compile(s) or cache load(s) inside the measured "
                "window: the warm-up did not cover it"
                % (meter.count - compiles_before))

    d0 = dispatch_count()
    periods = []
    window_asked = 0
    t0 = time.perf_counter()
    while True:
        p0 = time.perf_counter()
        sizes = drive(booster, period, dispatch_span)
        done = sum(sizes)
        with TraceAnnotation("sync"):
            jax.block_until_ready(booster.scores)
        p1 = time.perf_counter()
        window_asked += period
        periods.append((p0, p1, done))
        if done < period or p1 - t0 >= seconds or trace:
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    dispatches = dispatch_count() - d0
    peak = peak_bytes(devices)
    clock.append(("window", time.time()))

    # what the timed path produced
    trees = [tree_dict(t) for t in booster.models]
    scores = np.asarray(booster._training_score(), np.float32).reshape(-1)
    produced = reference.Produced(trees=trees, scores=scores,
                                  trees_asked=warm_trees + window_asked)
    window_trees = trees[warm_trees:]
    del booster, flush
    gc.collect()
    clock.append(("pull", time.time()))

    # one checked tree from each executable of the window's last period,
    # as train.py picks them
    ends = len(trees) - sum(sizes) + np.cumsum(sizes) - 1
    checked = sorted({int(ends[0]), *(int(e) for e in
                                      dict(zip(sizes, ends)).values())})
    t_ref = time.perf_counter()
    numbers = reference_sharded.compare(
        rows.bins, rows.label, params, produced,
        [t for t in checked if t >= 0], devices, control)
    correct, compared = reference.judge(numbers, cell.limits)
    clock.append(("reference", time.time()))
    print("train_sharded: train_tree_s %r setup_s %r; process start to "
          "driver %.1f s, then %s"
          % (window_s / max(len(window_trees), 1), setup_s,
             clock[0][1] - t_process,
             ", ".join("%s %.1f s" % (name, t - clock[i][1])
                       for i, (name, t) in enumerate(clock[1:]))),
          file=sys.stderr, flush=True)

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
        "hist_bins": max(len(b) for b in rows.upper_bounds),
        "shards": shards,
        "device_kind": devices[0].device_kind,
    }
    if control:
        record["control_correct"], record["control_compared"] = (
            reference.judge(reference.as_control(numbers), cell.limits))
    if trace:
        record["trace"] = reduce_trace(trace_dir)
    return record


def reduce_trace(trace_dir: str) -> dict:
    """The traced window chip by chip (one parse: `planes.reduced`).
    `busy_s` is the IDLEST chip's (the union of its operations' intervals,
    loops and conditionals left out), so the run's device block never
    reads busier than its idlest chip; the top operations and the idle
    gaps are that chip's."""
    red = planes.reduced(trace_mod.newest_xplane(trace_dir))
    busy = {plane: p["busy_s"] for plane, p in red["planes"].items()}
    return {"busy_s": busy[red["idlest"]], "window_s": red["window_s"],
            "breakdown": {
                "device_ops": red["device_ops"],
                "idle_gaps": red["idle_gaps"],
                "busy_by_plane": busy,
                "exchange_by_plane": planes.group_seconds(
                    red, [planes.EXCHANGE]),
                "own_hist_by_plane": planes.group_seconds(
                    red, planes.OWN_HIST),
                "collective_s": red["collective_s"]}}
