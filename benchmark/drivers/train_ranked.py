"""Driver of kind `train_ranked`: `train.py`'s steady boosting on a booster
whose rows come in queries.

What differs from `train.py`: the rows are `harness/data_ranked.py`'s (whole
queries, `--seed` orders them), the dataset carries
`Metadata(label, query_boundaries)` so that `create_objective` can make a
ranking objective of it, and what the window produced goes to
`harness/reference_ranked.py`.  The loop, the warm period, the window and
the record are `train.py`'s, by import where a function stands alone there.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from drivers.train import (HOST_SPANS, WARM_PERIODS, CompileMeter, drive,
                           peak_bytes, reduce_trace, tree_dict)
from harness import reference, reference_ranked, trace as trace_mod
from harness.data_ranked import make_ranked_rows


def build_booster(config: dict, rows, on_tpu: bool):
    """`train.build_booster` with the queries in the metadata."""
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
                 metadata=Metadata(label=rows.label,
                                   query_boundaries=rows.query_boundaries))
    objective = create_objective(cfg)
    objective.init(ds.metadata, ds.num_data)
    return create_boosting(cfg, ds, objective)


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        root: str, on_tpu: bool, break_booster=None,
        control: bool = False) -> dict:
    """-> the run's record, `train.run`'s and the queries' counts.
    `break_booster(booster)` is for tests that plant a fault under the
    timed path; `control` has the reference also compute the float8
    control and judge it in the program's place."""
    import jax
    from jax.profiler import TraceAnnotation
    from lightgbm_tpu.models.gbdt import dispatch_count

    config = cell.config
    params = config["params"]
    period = int(params["hist_reorder_every"])
    meter = CompileMeter()
    devices = jax.devices()[:cell.chips]

    rows = make_ranked_rows(config["data"], int(config["num_data"]),
                            int(config["num_queries"]),
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

    # one tree from each executable of the window's last period, as
    # `train.run` picks them
    ends = len(trees) - sum(sizes) + np.cumsum(sizes) - 1
    checked = sorted({int(ends[0]), *(int(e) for e in
                                      dict(zip(sizes, ends)).values())})
    t_ref = time.perf_counter()
    numbers = reference_ranked.compare(
        rows.bins, rows.label, rows.query_boundaries, params, produced,
        [t for t in checked if t >= 0], control)
    correct, compared = reference.judge(numbers, cell.limits)

    lengths = np.diff(rows.query_boundaries).astype(np.int64)
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
        "query_lengths": lengths,
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
