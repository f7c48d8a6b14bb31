"""A cell, a configuration and a metric added as files are found by name."""

import json
import os

from harness.cells import Cell


def test_dummy_cell_config_and_metric_found_by_name(tiny_root):
    bench = os.path.join(tiny_root, "benchmark")
    spec_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(spec_path) as fh:
        spec = json.load(fh)
    with open(os.path.join(bench, "configs", "dummy.json"), "w") as fh:
        json.dump({"num_data": 7, "params": {}}, fh)
    with open(os.path.join(bench, "workloads", "dummy_cell.json"), "w") as fh:
        json.dump({"limits": {}}, fh)
    with open(os.path.join(bench, "traffic", "dummy_mix.json"), "w") as fh:
        json.dump({"kind": "train"}, fh)
    with open(os.path.join(bench, "metrics", "dummy_metric.py"), "w") as fh:
        fh.write("def read(record):\n    return record['answer'] * 2.0\n")
    spec["configs"].append({"name": "dummy", "source": "x", "reduced": [],
                            "file": "benchmark/configs/dummy.json",
                            "why": "x"})
    spec["workloads"].append({"name": "dummy_cell", "config": "dummy",
                              "traffic": "dummy_mix", "chips": 1,
                              "why": "x"})
    for m in spec["per_layer"]:
        m["workloads"].append("dummy_cell")
    spec["per_layer"].append({"name": "dummy_metric", "unit": "x",
                              "better": "higher", "source": "program_counter",
                              "layer": "x", "moves": "train_tree_s",
                              "workloads": ["dummy_cell"]})
    with open(spec_path, "w") as fh:
        json.dump(spec, fh)
    cell = Cell(tiny_root, "dummy_cell")
    assert cell.config["num_data"] == 7
    assert cell.traffic == {"kind": "train"}
    assert hasattr(cell.driver(), "run")
    got = cell.per_layer({"answer": 21.0, "peak_bytes": 2 ** 30})
    assert got["dummy_metric"] == {"value": 42.0, "unit": "x"}
    # readers with nothing to read leave their metric out; the others report
    assert "hist_sweep_roofline" not in got
    assert got["peak_hbm_gib.train"]["value"] == 1.0
    e2e = cell.end_to_end({"train_tree_s": 1.5, "setup_s": 2.0})
    assert set(e2e) == {"train_tree_s", "setup_s"}
