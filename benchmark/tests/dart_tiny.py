"""The DART cell cut to a size the CPU trains in seconds, as `<cell>_tiny` in
a temporary checkout-shaped directory: for `test_dart_cell.py` here and
`tests/test_dart_cell.py`."""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "criteo64_dart_train"
# 30,000 rows x 39 features, 15 leaves, three of ten trees dropped; a period
# of 6 trees is the re-sorting step, K=3, K=2, the real cell's 1 + 8 + 7 in
# small; the window is two periods, trees 6-17, after a warm one.
# hist_impl=pallas is what makes the CPU take the chip's ordered path,
# iter_batch the chip's K (on the CPU `auto` is 1)
TINY = {"num_data": 30000,
        "params": {"num_leaves": 15, "min_data_in_leaf": 20,
                   "min_sum_hessian_in_leaf": 1.0, "num_iterations": 40,
                   "hist_reorder_every": 6, "drop_rate": 0.3,
                   "hist_impl": "pallas", "iter_batch": 3}}


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
    cfg["num_data"] = TINY["num_data"]
    cfg["data"]["block_rows"] = 10000
    cfg["params"].update(TINY["params"])
    return cfg


def make_root(root: str) -> str:
    """The benchmark's code copied under `root`, the DART cell cut to TINY as
    `<cell>_tiny` with the REAL cell's limits; -> the cell's name."""
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
