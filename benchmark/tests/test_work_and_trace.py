"""work.py on a hand-built tree; the trace reduction on a hand-made trace."""

import numpy as np
import pytest

from harness import trace, work
from harness.trace import Event


def hand_tree():
    # 100 rows: node 0 splits 100 -> 30 | 70; node 1 splits the 70 -> 60 | 10
    #   leaves: 0 (30 rows), 1 (60 rows), 2 (10 rows)
    return {"left_child": np.array([~0, ~1]), "right_child": np.array([1, ~2]),
            "leaf_count": np.array([30, 60, 10])}


def test_row_visits_root_plus_smaller_children():
    assert work.row_visits(hand_tree(), 100) == 100 + 30 + 10


def test_work_bytes_ops_and_least_time():
    w = work.work([hand_tree(), hand_tree()], 100, features=5)
    assert w["row_visits"] == 280
    assert w["bytes"] == 280 * (5 + 8 + 4)
    assert w["ops"] == 280 * 5 * 6
    least = work.least_seconds(w, "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(280 * 17 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_busy_is_the_union_not_the_sum():
    ev = [Event("a", 0, 100), Event("b", 50, 100), Event("c", 300, 50)]
    assert trace.union_seconds(ev) == pytest.approx(200e-9)
    assert trace.union_seconds(ev, 100, 320) == pytest.approx(70e-9)


def test_top_ops_and_kernel_seconds():
    ev = [Event("hist_kernel.1", 0, 100), Event("fusion", 100, 30),
          Event("hist_kernel.1", 200, 50)]
    assert trace.top_ops(ev)[0] == ["hist_kernel.1", pytest.approx(150e-9)]
    assert trace.kernel_seconds(ev, ["hist_kernel"]) == pytest.approx(150e-9)
    assert trace.kernel_seconds(ev, ["absent"]) == 0.0


def test_idle_gaps_are_named_by_the_covering_host_span():
    dev = [Event("k", 100, 100), Event("k", 400, 100)]
    host = [Event("dispatch", 0, 90), Event("flush", 210, 180),
            Event("sync", 500, 100)]
    gaps = dict(trace.idle_gaps(dev, host, 0, 600))
    assert gaps["dispatch"] == pytest.approx(100e-9)
    assert gaps["flush"] == pytest.approx(200e-9)
    assert gaps["sync"] == pytest.approx(100e-9)


def _metric(name):
    import os
    from conftest import BENCH
    from harness.cells import _module
    return _module(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("name", ["hist_sweep_roofline", "train_step_mfu",
                                  "device_idle_pct.train"])
def test_trace_readers_return_nothing_without_a_trace(name):
    assert _metric(name).read({"window_trees": [hand_tree()]}) is None


def test_a_gap_inside_a_dispatch_shows_as_idle():
    """The loop around the operations spans the gap between them; busy
    time is the operations' alone, and the step's share is taken over the
    traced window."""
    import importlib.util
    import os
    from conftest import BENCH
    spec = importlib.util.spec_from_file_location(
        "train_driver", os.path.join(BENCH, "drivers", "train.py"))
    train = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(train)
    dev = {"/device:TPU:0": [
        Event("%while.3 = (s32[]) while(...)", 0, 1000e6),
        Event("%leaf_histogram_blocklist.5 = f32[2] custom-call(...)",
              0, 400e6),
        Event("%fusion.1 = f32[2] fusion(...)", 600e6, 400e6)]}
    tr = train.reduce_trace(dev, [])
    assert tr["window_s"] == pytest.approx(1.0)
    assert tr["busy_s"] == pytest.approx(0.8)
    assert tr["breakdown"]["idle_gaps"] == [["unnamed", pytest.approx(0.2)]]
    record = {"trace": tr, "window_trees": [hand_tree()], "in_bag_rows": 100,
              "features": 5, "device_kind": "TPU v5 lite"}
    assert _metric("device_idle_pct.train").read(record) == \
        pytest.approx(20.0)
    least = 140 * 17 / 819e9
    assert _metric("train_step_mfu").read(record) == \
        pytest.approx(100.0 * least / 1.0)
    assert _metric("hist_sweep_roofline").read(record) == \
        pytest.approx(100.0 * least / 0.4)


def test_top_ops_leaves_out_loops_and_conditionals_and_shortens_names():
    ev = [Event("%while.3 = (s32[]) while(...)", 0, 1000),
          Event("%cond.1.clone = (f32[2]) conditional(...)", 0, 900),
          Event("%leaf_histogram_blocklist.5 = f32[2,4] custom-call(...)",
                10, 700)]
    assert trace.top_ops(ev) == [["%leaf_histogram_blocklist.5",
                                  pytest.approx(700e-9)]]
    assert trace.kernel_seconds(ev, ["%leaf_histogram"]) == \
        pytest.approx(700e-9)
