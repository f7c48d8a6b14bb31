"""Tiny cells in a temporary copy of the benchmark, for CPU tests.  Run by
path: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`."""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("LGBM_TPU_NO_COMPILE_CACHE", "1")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"num_data": 30000,
        "params": {"num_leaves": 15, "min_data_in_leaf": 20,
                   "min_sum_hessian_in_leaf": 1.0, "num_iterations": 40,
                   "hist_reorder_every": 4}}


def _load(path):
    with open(path) as fh:
        return json.load(fh)


def _dump(obj, path):
    with open(path, "w") as fh:
        json.dump(obj, fh)


@pytest.fixture(autouse=True, scope="session")
def no_compile_cache():
    """The tests compile on the CPU and keep no persistent cache."""
    import run as bench_run
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bench_run, "keep_compile_cache", lambda root: None)
        yield


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory: the benchmark's code copied, each real
    cell cut to 30,000 rows x 15 leaves as `<cell>_tiny`, found by name."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cells = []
    for w in spec["workloads"]:
        cfg_entry = [c for c in spec["configs"] if c["name"] == w["config"]][0]
        cfg = _load(os.path.join(ROOT, cfg_entry["file"]))
        cfg["num_data"] = TINY["num_data"]
        cfg["data"]["block_rows"] = 10000
        cfg["params"].update(TINY["params"])
        name = w["name"] + "_tiny"
        _dump(cfg, os.path.join(root, "benchmark", "configs", name + ".json"))
        shutil.copy(os.path.join(BENCH, "workloads", w["name"] + ".json"),
                    os.path.join(root, "benchmark", "workloads",
                                 name + ".json"))
        spec["configs"].append(dict(cfg_entry, name=name,
                                    file="benchmark/configs/%s.json" % name))
        cells.append(dict(w, name=name, config=name))
    spec["workloads"] = cells
    for m in spec["per_layer"]:
        m["workloads"] = [c["name"] for c in cells]
    _dump(spec, os.path.join(root, "BENCHMARK.json"))
    return root
