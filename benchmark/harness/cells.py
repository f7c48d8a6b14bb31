"""Finding a cell's files by the names in BENCHMARK.json.

Cell X is `benchmark/workloads/X.json` (the limits its comparison holds).
Its configuration is the `file` that BENCHMARK.json gives for the cell's
`config`; its traffic mix is `benchmark/traffic/<traffic>.json`, whose
`kind` picks `benchmark/drivers/<kind>.py`.  Per-layer metric M is the
reader `benchmark/metrics/M.py`: `read(record)` -> the number, or None
where the run left it nothing to read.  A later PR adds a cell, a
configuration, a traffic mix, a driver or a metric by adding files and one
entry in BENCHMARK.json, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, Optional


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, root: str, name: str):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmark")
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))
        entries = [w for w in self.spec["workloads"] if w["name"] == name]
        if not entries:
            raise KeyError("no workload %r in BENCHMARK.json" % name)
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.limits = _json(os.path.join(self.bench_dir, "workloads",
                                         name + ".json"))["limits"]
        cfg = [c for c in self.spec["configs"]
               if c["name"] == self.entry["config"]][0]
        self.config = _json(os.path.join(root, cfg["file"]))
        self.traffic = _json(os.path.join(self.bench_dir, "traffic",
                                          self.entry["traffic"] + ".json"))

    def driver(self):
        return _module(os.path.join(self.bench_dir, "drivers",
                                    self.traffic["kind"] + ".py"))

    def _listed(self, group: str):
        """The group's metrics that this cell reports."""
        return [m for m in self.spec[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def end_to_end(self, measures: Dict[str, float]) -> Dict[str, dict]:
        """The cell's end-to-end metrics, from what the driver timed."""
        return {m["name"]: {"value": measures[m["name"]], "unit": m["unit"]}
                for m in self._listed("end_to_end")}

    def per_layer(self, record: dict) -> Dict[str, dict]:
        """Every per-layer metric whose reader finds something to read."""
        out = {}
        for m in self._listed("per_layer"):
            reader = _module(os.path.join(self.bench_dir, "metrics",
                                          m["name"] + ".py"))
            value: Optional[float] = reader.read(record)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
