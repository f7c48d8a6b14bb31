"""A ranking cell cut to a size the CPU trains in seconds, as
`<cell>_tiny` in a temporary checkout-shaped directory: for
`test_ranked_cell.py` here and `tests/test_rank_cell.py`."""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "istella_rank_train"
# 300 queries of 1-60 documents x 12 features, 15 leaves; hist_impl=pallas
# is what makes the CPU take the chip's ordered path (block-list sweeps,
# the re-sort every period)
TINY = {"num_data": 9000, "num_queries": 300,
        "data": {"block_queries": 128,
                 "query_length": {"min": 1, "mean": 30.0, "max": 60,
                                  "cv": 0.35},
                 "columns": [
                     {"kind": "query", "columns": 3, "mean": 1.0,
                      "sigma": 1.0},
                     {"kind": "doc_score", "columns": 6, "within": 0.6},
                     {"kind": "counter", "columns": 3, "mean": 0.5,
                      "sigma": 1.5, "zero_rate": 0.4}],
                 "label": {"thresholds": [1.2, 1.7, 2.3, 3.0],
                           "weights": [[3, 2.5], [5, -1.5], [9, 1.0],
                                       [0, 0.6]],
                           "pairs": [[3, 4, 3.0], [1, 6, -2.0]]}},
        "params": {"num_leaves": 15, "min_data_in_leaf": 20,
                   "min_sum_hessian_in_leaf": 0.001, "num_iterations": 40,
                   "hist_reorder_every": 4, "hist_impl": "pallas"}}


def _load(path):
    with open(path) as fh:
        return json.load(fh)


def _dump(obj, path):
    with open(path, "w") as fh:
        json.dump(obj, fh)


def _entries():
    """BENCHMARK.json, the real cell's entry and its configuration's."""
    spec = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL][0]
    entry = [c for c in spec["configs"] if c["name"] == cell["config"]][0]
    return spec, cell, entry


def tiny_config() -> dict:
    """The real configuration with TINY laid over it."""
    cfg = _load(os.path.join(ROOT, _entries()[2]["file"]))
    cfg.update(num_data=TINY["num_data"], num_queries=TINY["num_queries"])
    cfg["data"].update(TINY["data"])
    cfg["params"].update(TINY["params"])
    return cfg


def make_root(root: str) -> str:
    """The benchmark's code copied under `root`, the ranking cell cut to
    TINY as `<cell>_tiny` with the REAL cell's limits; -> the cell's name."""
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec, cell, entry = _entries()
    name = CELL + "_tiny"
    _dump(tiny_config(),
          os.path.join(root, "benchmark", "configs", name + ".json"))
    shutil.copy(os.path.join(BENCH, "workloads", CELL + ".json"),
                os.path.join(root, "benchmark", "workloads", name + ".json"))
    spec["configs"] = [dict(entry, name=name,
                            file="benchmark/configs/%s.json" % name)]
    spec["workloads"] = [dict(cell, name=name, config=name)]
    for m in spec["per_layer"]:
        m["workloads"] = [name]
    _dump(spec, os.path.join(root, "BENCHMARK.json"))
    return name
