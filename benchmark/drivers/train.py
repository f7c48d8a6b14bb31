"""Driver of kind `train`: steady boosting on one booster.

Builds the booster the way `api.Booster.__init__` does (`create_objective`,
`create_boosting`, `device_type=tpu`) from arrays, warms it with whole
periods of trees (a period runs every executable the window uses), then
times whole periods through `GBDT.train_segment(remaining, is_eval=False)`,
waiting for the device only at period ends, until the clock passes
`--seconds`.  A period is the program's re-sort interval
(`hist_reorder_every`, in the configuration's parameters), which is also
its flush interval.  What the window produced (trees, score vector) then
goes to the plain reference, once the peak memory has been read and the
program's state freed.
"""

from __future__ import annotations

import gc
import os
import shutil
import time
from typing import List

import numpy as np

from harness import reference, trace as trace_mod
from harness.data import make_rows

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HOST_SPANS = ("dispatch", "flush", "sync")
WARM_PERIODS = 1        # on the booster that is then timed


class CompileMeter:
    """Backend compiles (persistent-cache loads included) counted and
    timed from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def build_booster(config: dict, rows, on_tpu: bool):
    """The program's objects from arrays, as `bench.py build_dataset` and
    `api.Booster.__init__` make them."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.binning import BinMapper
    from lightgbm_tpu.io.dataset import Dataset, Metadata
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.utils.device import resolve_device

    params = {k: str(v) for k, v in config["params"].items()}
    params["device_type"] = "tpu" if on_tpu else "cpu"
    cfg = Config.from_params(params)
    resolve_device(cfg.device_type)
    f = rows.bins.shape[0]
    mappers = [BinMapper(bin_upper_bound=b, num_bin=len(b), is_trivial=False,
                         sparse_rate=0.0) for b in rows.upper_bounds]
    ds = Dataset(bins=rows.bins, bin_mappers=mappers,
                 used_feature_map=np.arange(f, dtype=np.int32),
                 real_feature_index=np.arange(f, dtype=np.int32),
                 num_total_features=f,
                 feature_names=["Column_%d" % i for i in range(f)],
                 metadata=Metadata(label=rows.label))
    objective = create_objective(cfg)
    objective.init(ds.metadata, ds.num_data)
    return create_boosting(cfg, ds, objective)


def drive(booster, trees: int, annotate) -> List[int]:
    """`trees` iterations through train_segment, the loop of
    `cli.Application.train`; -> the trees of each dispatch it made before
    a stop."""
    sizes: List[int] = []
    while sum(sizes) < trees:
        with annotate("dispatch"):
            stop, k = booster.train_segment(trees - sum(sizes), is_eval=False)
        sizes.append(k)
        if stop:
            break
    return sizes


def tree_dict(t) -> dict:
    n = t.num_leaves
    return {"split_feature": np.asarray(t.split_feature[:n - 1]),
            "threshold_bin": np.asarray(t.threshold_bin[:n - 1]),
            "left_child": np.asarray(t.left_child[:n - 1]),
            "right_child": np.asarray(t.right_child[:n - 1]),
            "leaf_value": np.asarray(t.leaf_value[:n], np.float64),
            "leaf_count": np.asarray(t.leaf_count[:n])}


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        root: str, on_tpu: bool, break_booster=None,
        control: bool = False) -> dict:
    """-> the run's record.  `break_booster(booster)` is for tests that
    plant a fault under the timed path; `control` has the reference also
    compute the float8 control and judge it in the program's place."""
    import jax
    from jax.profiler import TraceAnnotation
    from lightgbm_tpu.models.gbdt import dispatch_count

    config = cell.config
    params = config["params"]
    period = int(params["hist_reorder_every"])
    meter = CompileMeter()
    devices = jax.devices()[:cell.chips]

    rows = make_rows(config["data"], int(config["num_data"]),
                     int(params["max_bin"]), seed)
    booster = build_booster(config, rows, on_tpu)
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
    setup_compile_s, compiles_before = meter.seconds, meter.count

    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    d0 = dispatch_count()
    periods = []
    window_asked = 0
    t0 = time.perf_counter()
    while True:
        p0 = time.perf_counter()
        sizes = drive(booster, period, TraceAnnotation)
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
    if meter.count != compiles_before:
        raise RuntimeError(
            "%d backend compile(s) or cache load(s) inside the measured "
            "window: the warm-up did not cover it"
            % (meter.count - compiles_before))
    peak = peak_bytes(devices)

    # what the timed path produced
    trees = [tree_dict(t) for t in booster.models]
    scores = np.asarray(booster._training_score(), np.float32).reshape(-1)
    produced = reference.Produced(trees=trees, scores=scores,
                                  trees_asked=warm_trees + window_asked)
    window_trees = trees[warm_trees:]
    del booster, flush
    gc.collect()

    # the trees whose growth is recomputed, in the window's last period:
    # the last tree of its first dispatch (the re-sort's) and of the last
    # dispatch of each size, so one from each executable of a period, and
    # the window's last tree
    ends = len(trees) - sum(sizes) + np.cumsum(sizes) - 1
    checked = sorted({int(ends[0]), *(int(e) for e in
                                      dict(zip(sizes, ends)).values())})
    t_ref = time.perf_counter()
    numbers = reference.compare(rows.bins, rows.label, params, produced,
                                [t for t in checked if t >= 0], control)
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


def reduce_trace(dev: dict, host: list) -> dict:
    """Busy seconds averaged over the chips used, the traced window, and
    the breakdown.  Busy is the union of the OPERATIONS' intervals: the
    loops and conditionals around them are left out, so a gap inside a
    dispatch shows.  The window is the host's `dispatch`..`sync` extent
    where the trace has those spans on its clock, else the first to the
    last device operation."""
    dev = {plane: trace_mod.operations(evs) for plane, evs in dev.items()}
    events = [e for evs in dev.values() for e in evs]
    if not events:
        raise RuntimeError("the trace holds no device operation")
    if host:
        lo = min(e.start_ns for e in host)
        hi = max(e.end_ns for e in host)
    else:
        lo = min(e.start_ns for e in events)
        hi = max(e.end_ns for e in events)
    busy = float(np.mean([trace_mod.union_seconds(evs, lo, hi)
                          for evs in dev.values()]))
    first = next(iter(dev.values()))
    return {"busy_s": busy, "window_s": (hi - lo) / 1e9, "events": events,
            "breakdown": {
                "device_ops": trace_mod.top_ops(events),
                "idle_gaps": trace_mod.idle_gaps(first, host, lo, hi)}}
