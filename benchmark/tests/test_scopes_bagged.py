"""`harness/scopes_bagged.py` on the CPU: the draw's span read from a real
trace of the tiny bagged job with its stats, nested inside
`lgbm.host_inputs` whose self time then leaves it out; the grouping's
arithmetic on a hand-made reduction.  `tests/test_bag_cell.py` imports
these."""

import glob
import os

import pytest

import bagged_tiny
from harness import scopes, scopes_bagged


@pytest.fixture(scope="module")
def traced_spans(tmp_path_factory):
    """The program's host spans of 7 trees of the tiny bagged job (draws
    before trees 0, 3 and 6), the draw's among them, nested."""
    import jax
    from drivers import train_bagged
    from harness.data import make_rows
    cfg = bagged_tiny.tiny_config()
    rows = make_rows(cfg["data"], cfg["num_data"], 255, 11)
    booster = train_bagged.build_booster(cfg, rows, on_tpu=False)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=options):
        bags = {}
        train_bagged.drive(booster, 7, jax.profiler.TraceAnnotation, 3, bags)
        assert len(booster.models) == 7 and sorted(bags) == [0, 1, 2]
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    host = scopes.read_trace(path).host
    assert not [s for s in host if s.name == "lgbm.bag_draw"]
    host += scopes_bagged.read_spans(path,
                                     scopes_bagged.NAMES["host_spans"])
    return scopes.nest(host)


def test_the_draw_is_read_with_its_stats_inside_host_inputs(traced_spans):
    draws = [s for s in traced_spans if s.name == "lgbm.bag_draw"]
    # (the reader leaves a stat of value 0 out: tree 0's `iter`)
    assert [(s.stats.get("iter", 0), s.stats["rows"], s.stats["in_bag"])
            for s in draws] == [(0, 30000, 24000), (3, 30000, 24000),
                                (6, 30000, 24000)]
    for s in draws:
        parent = traced_spans[s.parent]
        assert parent.name == "lgbm.host_inputs"
        # the parent's self time no longer holds the draw
        assert parent.self_ns <= parent.dur_ns - s.dur_ns + 1.0
        assert s.self_ns == s.dur_ns > 0


def test_the_flushes_carry_the_sampling_counters(traced_spans):
    flushes = [s.stats for s in traced_spans if s.name == "lgbm.flush"]
    assert flushes
    assert sum(s.get("bag_draws", 0) for s in flushes) == 3
    for s in flushes:
        assert (s["bag_window"], s["bag_in_bag"], s["feat_used"]) == (
            24576, 24000, 31)


def _span(name, start, dur, self_ns=None, **stats):
    s = scopes.Span(name, float(start), float(dur), stats)
    s.self_ns = float(dur if self_ns is None else self_ns)
    return s


def test_grouping_arithmetic(monkeypatch):
    red = {"has_scopes": True,
           "device_s": {"lgbm.bag_arrange": 9.0, "lgbm.resort": 3.0,
                        "lgbm.oob_descent": 1.5, "lgbm.partition": 4.0,
                        "lgbm.grow": 0.5, "unscoped": 0.25},
           "spans_in_window": [
               _span("lgbm.bag_draw", 0, 2e9),
               _span("lgbm.bag_draw", 5e9, 1e9),
               _span("lgbm.host_inputs", 0, 3e9, self_ns=1e9),
               _span("lgbm.enqueue", 3e9, 5e8),
               _span("lgbm.flush", 4e9, 1e9, bag_window=1000, bag_in_bag=990,
                     bag_draws=2, feat_used=31),
               _span("lgbm.flush", 7e9, 1e9, bag_window=1000, bag_in_bag=990,
                     bag_draws=1, feat_used=31),
               _span("lgbm.flush", 9e9, 0, bag_window=1000, bag_in_bag=990,
                     feat_used=31)]}
    monkeypatch.setattr(scopes_bagged, "for_record", lambda record: red)
    record = {"trace": {}, "window_tree_count": 15}
    read = lambda name: scopes_bagged.tree_seconds(record, name)
    assert read("bag_arrange_tree_s") == 9.0 / 15
    assert read("resort_tree_s.bag") == 3.0 / 15
    assert read("oob_descent_tree_s") == 1.5 / 15
    assert read("partition_tree_s.bag") == 4.5 / 15
    assert read("hist_tree_s.bag") == 0.0
    assert read("bag_draw_tree_s") == 3.0 / 15
    assert read("host_segment_tree_s.bag") == 1.5 / 15
    assert read("flush_tree_s.bag") == 2.0 / 15
    assert scopes_bagged.flush_counters(record) == {
        "bag_window": 1000, "bag_in_bag": 990, "bag_draws": 3,
        "feat_used": 31}
    # a program that names no draw, a trace without scopes, no trace
    red["spans_in_window"] = [s for s in red["spans_in_window"]
                              if s.name != "lgbm.bag_draw"]
    assert read("bag_draw_tree_s") is None
    red["has_scopes"] = False
    assert read("bag_arrange_tree_s") is None
    for s in red["spans_in_window"]:
        s.stats.clear()                 # a job that does not sample
    assert scopes_bagged.flush_counters(record) is None
    monkeypatch.setattr(scopes_bagged, "for_record", lambda record: None)
    assert read("flush_tree_s.bag") is None
    assert scopes_bagged.flush_counters(record) is None


def test_every_reader_of_the_cell_finds_nothing_in_an_untraced_record():
    from harness.cells import Cell
    cell = Cell(bagged_tiny.ROOT, bagged_tiny.CELL)
    listed = [m["name"] for m in cell.spec["per_layer"]
              if m.get("workloads") == [bagged_tiny.CELL]]
    assert len(listed) == 19
    got = cell.per_layer({"peak_bytes": 2 ** 31, "setup_compile_s": 3.5,
                          "dispatches": 7, "window_tree_count": 15})
    assert got == {"peak_hbm_gib.bag": {"value": 2.0, "unit": "GiB"},
                   "setup_compile_s.bag": {"value": 3.5, "unit": "s"},
                   "trees_per_dispatch.bag": {"value": 15 / 7,
                                              "unit": "trees"}}
