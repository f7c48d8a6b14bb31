"""The class-wise cell cut to a size the CPU trains in seconds, as
`<cell>_tiny` in a temporary checkout-shaped directory: for
`test_multi_cell.py` here and `tests/test_multi_cell.py`."""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "mnist8m_multi_train"
# 16,384 rows (two row blocks of the sweep kernel) of 6 x 6 images, 36
# features, 7 leaves; the window is the real cell's, one period of two
# iterations (the re-sorting step, then the plain one) after a warm one.
# hist_impl=pallas is what makes the CPU take the chip's ordered path
TINY = {"num_data": 16384,
        "params": {"num_leaves": 7, "min_data_in_leaf": 20,
                   "min_sum_hessian_in_leaf": 1.0, "hist_impl": "pallas"},
        "data": {"block_rows": 4096,
                 "image": {"side": 6, "box": [1, 5], "width": 0.8},
                 "label": {"regions": 3, "sharpness": 6.0}}}


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


def tiny_config(classes: int = 10) -> dict:
    """The real configuration with TINY laid over it, at `classes`."""
    cfg = _load(os.path.join(ROOT, _entries()[2]["file"]))
    cfg["num_data"] = TINY["num_data"]
    cfg["params"].update(TINY["params"], num_class=classes)
    data = cfg["data"]
    data["block_rows"] = TINY["data"]["block_rows"]
    data["classes"] = classes
    data["image"].update(TINY["data"]["image"])
    data["label"].update(TINY["data"]["label"])
    return cfg


def make_root(root: str, classes: int = 10) -> str:
    """The benchmark's code copied under `root`, the class-wise cell cut to
    TINY at `classes` as `<cell>_tiny` with the REAL cell's limits; -> the
    cell's name."""
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec, cell, entry = _entries()
    name = CELL + "_tiny"
    _dump(tiny_config(classes),
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
