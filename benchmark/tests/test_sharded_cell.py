"""The four-chip cell at a tiny size, as `<cell>_tiny` through `run_cell`.
The tests' process has one CPU device, so each run is a process of its own
with four virtual ones (sharded_worker.py)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "criteo64_train_4chip_tiny"


def _worker(tiny_root, mode, seed=2 ** 31 + 77):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "sharded_worker.py"), tiny_root,
         CELL, mode, str(seed)], capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_well_formed(tiny_root):
    r = _worker(tiny_root, "sound")
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] == 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tree_s", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 4
    assert r["checked_trees"] == [4, 7]
    assert list(r)[-1] == "compared"


def test_float8_control_is_not_correct(tiny_root):
    r = _worker(tiny_root, "control")
    assert r["correct"] is True, r["compared"]
    assert r["control_correct"] is False, r["control_compared"]


@pytest.mark.parametrize("fault", ["shard_left_out", "no_exchange"])
def test_planted_fault_is_not_correct(tiny_root, fault):
    r = _worker(tiny_root, fault)
    assert r["correct"] is False, r["compared"]
    assert r["compared"]["leaf_count_gap"][0] > 0.2


def test_one_device_cannot_run_the_cell(tiny_root):
    """In this process (one CPU device) the cell ends with an error at
    once, not with a result on fewer shards."""
    import run as bench_run
    with pytest.raises((SystemExit, ValueError)):
        bench_run.run_cell(tiny_root, CELL, seed=1, seconds=0.0, trace=False,
                           require_tpu=False)
