"""The reduction of a trace by the program's own names (harness/scopes.py),
on a hand-made event list and on a sample recorded on the chip."""

import json
import os

import pytest

import phase_table
from harness import scopes
from harness.scopes import Op, Span, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
S = 1e9     # a second, in the trace's nanoseconds


def op(name, start, dur, op_name=""):
    return Op(name, start * S, dur * S, op_name)


def span(name, start, dur, thread="main", **stats):
    return Span(name, start * S, dur * S, stats, thread)


def hand_made():
    """Two trees in a window of 10 s (the benchmark's `dispatch` 1..9 and
    `sync` 9..11): operations before, across and after the window's
    edges, a loop around some, a gap while the host pulls."""
    stack = "jit(step)/jit(main)/while/body/"
    device = [
        op("%while.1 = (...) while(...)", 0.0, 9.0),             # container
        op("%fusion.1 = f32[8] fusion(...)", 0.0, 2.0,
           stack + "lgbm.objective/mul"),                 # 1 s in window
        op("%leaf_histogram_blocklist.5 = f32[...] custom-call(...)", 2.0,
           3.0, stack + "lgbm.grow/while/body/lgbm.hist_sweep/"
           "jit(leaf_histogram_blocklist)/leaf_histogram_blocklist/"
           "pallas_call"),
        op("%pad.135 = u8[48,64] pad(...)", 5.0, 1.0,
           stack + "lgbm.grow/while/body/lgbm.hist_sweep/jit(f)/pad"),
        op("%fusion.6 = s32[64] fusion(...)", 6.0, 0.5,
           stack + "lgbm.grow/while/body/select_n"),      # grow, no deeper
        op("%copy.3 = f32[8] copy(...)", 6.5, 0.5, "jit(step)/copy"),
        # 7.0 .. 8.0: nothing runs (the host pulls the trees)
        op("%sort.2 = (...) sort(...)", 8.0, 4.0,
           stack + "lgbm.resort/sort"),                   # 3 s in window
    ]
    host = [
        span("dispatch", 1.0, 8.0), span("sync", 9.0, 2.0),
        span("lgbm.segment", 1.0, 8.0, iter=16, k=2),
        span("lgbm.host_inputs", 1.0, 0.25),
        span("lgbm.enqueue", 1.5, 0.5, kind="scan", k=2),
        span("lgbm.flush", 6.75, 1.5, trees=2, bytes=4640),
        span("lgbm.flush_pull", 7.0, 1.0),
        span("lgbm.flush_unpack", 8.0, 0.25),
        span("lgbm.segment", 20.0, 1.0, iter=18, k=1),    # after the window
        span("lgbm.enqueue", 1.6, 0.1, "other", kind="arrange", k=0),
    ]
    return Trace({"/device:TPU:0": device}, host)


def test_last_lgbm_component_names_the_scope():
    assert scopes.scope_of("jit(a)/lgbm.grow/while/body/lgbm.hist_sweep/"
                           "jit(k)/k/pallas_call") == "lgbm.hist_sweep"
    assert scopes.scope_of("jit(a)/lgbm.grow/cond/select_n") == "lgbm.grow"
    assert scopes.scope_of("jit(a)/copy") == "unscoped"
    assert scopes.scope_of("") == "unscoped"


def test_device_seconds_clip_to_the_window_and_skip_containers():
    red = scopes.reduce(hand_made())
    assert red["window_s"] == pytest.approx(10.0)
    assert red["device_s"] == pytest.approx({
        "lgbm.objective": 1.0, "lgbm.hist_sweep": 4.0, "lgbm.grow": 0.5,
        "unscoped": 0.5, "lgbm.resort": 3.0})
    # the loop's own event is not an operation: the sum is the busy time
    assert sum(red["device_s"].values()) == pytest.approx(red["busy_s"])
    assert red["busy_s"] == pytest.approx(9.0)


def test_groups_and_unscoped_share():
    red = scopes.reduce(hand_made())
    got = {m: scopes.device_group_seconds(red, m)
           for m in scopes.NAMES["device_groups"]}
    assert got == pytest.approx({
        "objective_tree_s": 1.0, "hist_tree_s": 4.0, "gain_scan_tree_s": 0.0,
        "partition_tree_s": 0.5, "score_update_tree_s": 0.0,
        "resort_tree_s": 3.0})
    assert scopes.unscoped_pct(red) == pytest.approx(100 * 0.5 / 9.0)
    # the phase metrics and the unscoped seconds together are all the
    # operation time
    assert sum(got.values()) + red["device_s"]["unscoped"] \
        == pytest.approx(red["busy_s"])


def test_spans_nest_on_their_thread_and_keep_self_time():
    spans = scopes.reduce(hand_made())["spans"]
    by = {(s.name, s.start_ns / S): s for s in spans}
    seg = by[("lgbm.segment", 1.0)]
    assert seg.parent is not None and spans[seg.parent].name == "dispatch"
    for child in ("lgbm.host_inputs", "lgbm.flush"):
        (c,) = [s for s in spans if s.name == child]
        assert spans[c.parent] is seg
    pull = by[("lgbm.flush_pull", 7.0)]
    assert spans[pull.parent].name == "lgbm.flush"
    assert spans[pull.parent].self_ns / S == pytest.approx(1.5 - 1.0 - 0.25)
    # segment: 8 s less host_inputs 0.25, enqueue 0.5, flush 1.5
    assert seg.self_ns / S == pytest.approx(8.0 - 0.25 - 0.5 - 1.5)
    # a span on another thread has no parent here
    assert by[("lgbm.enqueue", 1.6)].parent is None


def test_host_metrics_take_the_window_only():
    red = scopes.reduce(hand_made())
    assert scopes.host_group_seconds(red, "flush_tree_s") \
        == pytest.approx(1.5)
    assert scopes.host_group_seconds(red, "host_segment_tree_s") \
        == pytest.approx(5.75 + 0.25 + 0.5 + 0.1)
    assert sum(s.stats["k"] for s in red["spans_in_window"]
               if s.name == "lgbm.segment") == 2


def test_idle_gap_goes_to_the_innermost_program_span():
    red = scopes.reduce(hand_made())
    # 7..8 s, the one gap: segment, flush and flush_pull all cover it;
    # the pull is the innermost
    assert red["idle_by_span"] == [["lgbm.flush_pull", pytest.approx(1.0)]]


def test_a_trace_without_scopes_reads_nothing():
    tr = hand_made()
    bare = Trace({p: [Op(e.name, e.start_ns, e.dur_ns, "") for e in evs]
                  for p, evs in tr.device.items()},
                 [s for s in tr.host if not s.name.startswith("lgbm.")])
    red = scopes.reduce(bare)
    assert red["busy_s"] == pytest.approx(9.0)
    assert all(scopes.device_group_seconds(red, m) is None
               for m in scopes.NAMES["device_groups"])
    assert scopes.unscoped_pct(red) is None
    assert all(scopes.host_group_seconds(red, m) is None
               for m in scopes.NAMES["host_groups"])


def test_untraced_record_reads_nothing():
    assert scopes.for_record({"window_tree_count": 16}) is None
    assert scopes.tree_seconds({"window_tree_count": 16},
                               "hist_tree_s") is None


def test_reading_a_profile_file(tmp_path):
    """A two-plane XSpace written with the reader's own schema: the name
    stack is a stat of the operation's METADATA, a stat may give its
    value by reference, times are the line's start plus an offset."""
    space = scopes._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "device_offset_ps")):
        dev.stat_metadata.add(key=key).value.name = name
    meta = dev.event_metadata.add(key=7).value
    meta.name = "%fusion.6 = u8[8,39] fusion(...)"
    meta.stats.add(metadata_id=1, str_value="jit(step)/lgbm.resort/gather:")
    dev.event_metadata.add(key=8).value.name = "%copy.1 = f32[8] copy(...)"
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=7, offset_ps=2_000_000, duration_ps=500_000) \
        .stats.add(metadata_id=2, int64_value=5)
    ops.events.add(metadata_id=8, offset_ps=3_000_000, duration_ps=250_000)
    dev.lines.add(name="XLA Modules").events.add(metadata_id=7)
    host = space.planes.add(name="/host:CPU")
    for key, name in ((1, "kind"), (2, "k"), (3, "scan")):
        host.stat_metadata.add(key=key).value.name = name
    host.event_metadata.add(key=1).value.name = "lgbm.enqueue"
    host.event_metadata.add(key=2).value.name = "$gbdt.py:1 not_ours"
    line = host.lines.add(name="python3", timestamp_ns=0)
    e = line.events.add(metadata_id=1, offset_ps=4_000_000, duration_ps=1000)
    e.stats.add(metadata_id=1, ref_value=3)
    e.stats.add(metadata_id=2, int64_value=8)
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=1)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    tr = scopes.read_trace(str(path))
    assert tr.device == {"/device:TPU:0": [
        Op("%fusion.6 = u8[8,39] fusion(...)", 3000.0, 500.0,
           "jit(step)/lgbm.resort/gather:"),
        Op("%copy.1 = f32[8] copy(...)", 4000.0, 250.0, "")]}
    assert tr.host == [Span("lgbm.enqueue", 4000.0, 1.0,
                            {"kind": "scan", "k": 8}, "python3")]


def test_table_and_sample_round_trip(tmp_path):
    tr = hand_made()
    text = phase_table.table(tr, 5)
    assert "lgbm.hist_sweep" in text and "2 trees" in text
    assert "lgbm.enqueue scan" in text and "k=2" in text
    again = phase_table.from_sample(
        json.loads(json.dumps(phase_table.sample(tr, 100))))
    assert scopes.reduce(again)["device_s"] \
        == pytest.approx(scopes.reduce(tr)["device_s"])


# -- a slice of a trace recorded on the chip -------------------------------
# `python benchmark/phase_table.py .bench_trace --sample ...` after a traced
# run of criteo64_train on a TPU v5e (PR 25, chip call 2): 400 consecutive
# operations from the middle of the period, every host span.
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "sample_trace.json")) as fh:
        return phase_table.from_sample(json.load(fh))


def test_recorded_operations_all_carry_a_registered_scope(recorded):
    (ops,) = recorded.device.values()
    assert len(ops) == 400
    found = {scopes.scope_of(e.op_name) for e in ops}
    assert found <= set(scopes.NAMES["device_scopes"]) | {"unscoped"}
    # the slice crosses a tree's boundary: the end of one grow scan, the
    # score update, the next tree's objective, root sweep and first splits
    assert {"lgbm.hist_sweep", "lgbm.hist_root", "lgbm.partition",
            "lgbm.block_list", "lgbm.gain_scan", "lgbm.tree_update",
            "lgbm.score_update", "lgbm.objective"} <= found
    # a kernel's event keeps the name the accepted sweep reader matches,
    # and the bin matrix's pad sits in its sweep's scope
    by_name = {}
    for e in ops:
        by_name.setdefault(e.name.split(".")[0], set()).add(
            scopes.scope_of(e.op_name))
    assert by_name["%leaf_histogram_blocklist"] \
        == {"lgbm.hist_sweep", "lgbm.hist_root"}
    pads = {e.name.split(" = ")[0]: scopes.scope_of(e.op_name) for e in ops
            if e.name.startswith("%pad.") and e.dur_ns > 1e6}
    assert pads == {"%pad.135": "lgbm.hist_sweep",
                    "%pad.132": "lgbm.hist_root"}


def test_recorded_slice_sums_to_its_busy_time(recorded):
    red = scopes.reduce(recorded)
    assert red["has_scopes"]
    assert sum(red["device_s"].values()) == pytest.approx(red["busy_s"],
                                                          rel=1e-6)
    assert scopes.unscoped_pct(red) < 1.0


def test_recorded_spans_count_the_period(recorded):
    red = scopes.reduce(recorded)
    inside = red["spans_in_window"]
    enq = [s for s in inside if s.name == "lgbm.enqueue"]
    assert [(s.stats["kind"], s.stats["k"]) for s in enq] \
        == [("resort", 1), ("scan", 8), ("scan", 7)]
    seg = [s for s in inside if s.name == "lgbm.segment"]
    assert sum(s.stats["k"] for s in seg) == 16
    assert [s.stats["iter"] for s in seg] == [16, 17, 25]
    (flush,) = [s for s in inside if s.name == "lgbm.flush"]
    assert flush.stats["trees"] == 16 and flush.stats["bytes"] == 40000
    (pull,) = [s for s in inside if s.name == "lgbm.flush_pull"]
    assert red["spans"][pull.parent] is flush
    # the flush is the pull: the host waits there for the device
    assert pull.dur_ns / flush.dur_ns > 0.99
