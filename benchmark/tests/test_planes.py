"""The plane-by-plane reduction (harness/planes.py) and the six readers of
the four-chip cell, on a made trace with four device planes."""

import importlib.util
import os

import pytest

from harness import planes
from harness.scopes import Op, Span, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
S = 1e9
STACK = "jit(step)/jit(main)/while/body/lgbm.grow/while/body/"


def op(name, start, dur, op_name=""):
    return Op(name, start * S, dur * S, op_name)


def four_planes():
    """A window of 10 s (`dispatch` 0..9, `sync` 9..10).  Chip n sweeps
    for 4 + n/2 s and then waits in the all-reduce for the slowest, so
    its exchange lasts 2.5 - n/2 s; chip 3 has an all-reduce that lost
    its scope, and chip 0 a gap of 1 s."""
    device = {}
    for n in range(4):
        sweep = 4.0 + n / 2.0
        wait = 2.5 - n / 2.0
        device["/device:TPU:%d" % n] = [
            op("%while.3 = (...) while(...)", 0.0, 9.5),      # container
            op("%leaf_histogram_blocklist.17 = f32[...] custom-call(...)",
               0.0, sweep, STACK + "lgbm.hist_sweep/jit(k)/k/pallas_call"),
            op("%psum.4 = f32[39,241,3]{1,0,2} all-reduce(%c.8), channel_id=1",
               sweep, wait,
               STACK + "lgbm.hist_sweep/lgbm.hist_exchange/psum"),
            op("%fusion.9 = s32[] fusion(...)", 6.5, 0.5,
               STACK + "lgbm.block_list/reduce_sum"),
            op("%pmax.7 = s32[]{:T(128)} all-reduce-start(%g.1)", 7.0, 0.25,
               STACK + ("lgbm.block_list/lgbm.hist_exchange/pmax" if n < 3
                        else "pmax")),
            op("%sort.2 = (...) sort(...)", 7.25 + (1.0 if n == 0 else 0.0),
               1.0, "jit(step)/lgbm.resort/sort"),
        ]
    host = [Span("dispatch", 0.0, 9.0 * S, {}, "main"),
            Span("sync", 9.0 * S, 1.0 * S, {}, "main")]
    return Trace(device, host)


def test_planes_are_kept_apart():
    red = planes.reduce(four_planes())
    assert red["window_s"] == pytest.approx(10.0)
    assert sorted(red["planes"]) == ["/device:TPU:%d" % n for n in range(4)]
    assert planes.group_seconds(red, [planes.EXCHANGE]) == pytest.approx({
        "/device:TPU:0": 2.75, "/device:TPU:1": 2.25, "/device:TPU:2": 1.75,
        "/device:TPU:3": 1.0})
    own = planes.group_seconds(red, planes.OWN_HIST)
    assert own == pytest.approx({"/device:TPU:0": 4.5, "/device:TPU:1": 5.0,
                                 "/device:TPU:2": 5.5, "/device:TPU:3": 6.0})
    # every chip is busy 8.25 s of the 10; the loop's event is no operation
    assert all(p["busy_s"] == pytest.approx(8.25)
               for p in red["planes"].values())
    assert red["has_scopes"]
    # chip 3's pmax lost its scope: 0.25 s of the collectives' 8 s
    assert red["collective_s"] == pytest.approx(
        {"in_exchange_scope": 7.75, "outside": 0.25})
    # the idlest chip's gaps (all are busy alike: the first), by host span
    assert red["idlest"] == "/device:TPU:0"
    assert red["idle_gaps"] == [["dispatch", pytest.approx(1.0)],
                                ["sync", pytest.approx(0.75)]]


def test_no_scope_means_nothing_to_read(monkeypatch):
    tr = four_planes()
    bare = Trace({k: [Op(e.name, e.start_ns, e.dur_ns, "") for e in v]
                  for k, v in tr.device.items()}, tr.host)
    red = planes.reduce(bare)
    assert not red["has_scopes"]
    monkeypatch.setattr(planes, "reduced", lambda path: red)
    monkeypatch.setattr(planes.trace, "newest_xplane", lambda d: "x")
    assert planes.for_record({"trace": {"window_s": 10.0}}) is None
    assert planes.for_record({}) is None


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def record(monkeypatch):
    red = planes.reduce(four_planes())
    monkeypatch.setattr(planes, "reduced", lambda path: red)
    monkeypatch.setattr(planes.trace, "newest_xplane", lambda d: "x")
    tree = {"left_child": [-1], "right_child": [-2],
            "leaf_count": [3000000, 1000000], "leaf_value": [0.1, -0.1]}
    return {"trace": {"busy_s": 8.25, "window_s": 10.0},
            "window_trees": [tree, tree], "window_tree_count": 2,
            "in_bag_rows": 4000000, "features": 39, "hist_bins": 241,
            "shards": 4, "device_kind": "TPU v5 lite",
            "peak_bytes": 5 * 2 ** 30}


def test_the_six_readers(record):
    assert _reader("exchange_tree_s")(record) == pytest.approx(2.75 / 2)
    assert _reader("shard_skew_pct")(record) == pytest.approx(25.0)
    assert _reader("device_idle_pct.data4")(record) == pytest.approx(17.5)
    assert _reader("peak_hbm_gib.data4")(record) == pytest.approx(5.0)
    # two trees x two leaves x [39, 241, 3] f32, 2 x 3/4 of each, at 200 GB/s
    wire = 1.5 * 4 * 39 * 241 * 3 * 4
    assert _reader("exchange_roofline")(record) == pytest.approx(
        100.0 * wire / 200e9 / 2.75)
    # all rows' work (5M visits a tree) over four chips, bound by bytes
    least = 2 * 5000000 * (39 + 12) / 819e9 / 4
    assert _reader("train_step_mfu.data4")(record) == pytest.approx(
        100.0 * least / 10.0)


@pytest.mark.parametrize("name", [
    "train_step_mfu.data4", "exchange_tree_s", "exchange_roofline",
    "shard_skew_pct", "device_idle_pct.data4"])
def test_untraced_run_reads_nothing(name):
    assert _reader(name)({"peak_bytes": 1, "window_trees": [],
                          "window_tree_count": 0}) is None
