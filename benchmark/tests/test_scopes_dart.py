"""`harness/scopes_dart.py` on the CPU: the lottery's span read from a real
trace of the tiny DART job with its stats, nested inside `lgbm.host_inputs`
whose self time then leaves it out, the flushes' counters; the grouping's
arithmetic on a hand-made reduction.  `tests/test_dart_cell.py` imports
these."""

import glob
import os

import pytest

import dart_tiny
from harness import scopes, scopes_bagged, scopes_dart


@pytest.fixture(scope="module")
def traced_dart(tmp_path_factory):
    """The program's host spans of 7 trees of the tiny DART job, the
    lottery's among them, nested."""
    import jax
    from drivers import train_dart
    from harness.data import make_rows
    cfg = dart_tiny.tiny_config()
    rows = make_rows(cfg["data"], cfg["num_data"], 255, 11)
    booster = train_dart.build_booster(cfg, rows, on_tpu=False)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=options):
        train_dart.drive(booster, 7, jax.profiler.TraceAnnotation)
        assert len(booster.models) == 7
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    host = scopes.read_trace(path).host
    assert not [s for s in host if s.name == "lgbm.dart_draw"]
    host += scopes_bagged.read_spans(path, scopes_dart.NAMES["host_spans"])
    return scopes.nest(host), booster.drop_history()


def test_the_lottery_is_read_with_its_stats_inside_host_inputs(traced_dart):
    spans, drops = traced_dart
    draws = [s for s in spans if s.name == "lgbm.dart_draw"]
    # (the reader leaves a stat of value 0 out: tree 0's `iter` and `k`)
    assert [(s.stats.get("iter", 0), s.stats.get("k", 0))
            for s in draws] == [(t, len(d)) for t, d in enumerate(drops)]
    for s in draws:
        parent = spans[s.parent]
        assert parent.name == "lgbm.host_inputs"
        assert s.self_ns == s.dur_ns > 0
    flushes = [s.stats for s in spans if s.name == "lgbm.flush"]
    assert flushes
    assert sum(s.get("dart_drops", 0) for s in flushes) == sum(
        len(d) for d in drops)
    assert flushes[-1]["dart_bank_rows"] == 7
    assert all(s["dart_bank_cap"] >= 40 for s in flushes)


def _span(name, start, dur, self_ns=None, **stats):
    s = scopes.Span(name, float(start), float(dur), stats)
    s.self_ns = float(dur if self_ns is None else self_ns)
    return s


def test_dart_grouping_arithmetic(monkeypatch):
    red = {"has_scopes": True,
           "device_s": {"lgbm.dart_drop": 0.6, "lgbm.dart_normalize": 0.9,
                        "lgbm.dart_replay": 0.25, "lgbm.dart_carry": 2.0,
                        "lgbm.dart_bank": 0.125, "lgbm.resort": 4.0,
                        "lgbm.score_update": 0.5, "unscoped": 0.25},
           "spans_in_window": [
               _span("lgbm.dart_draw", 0, 2e6),
               _span("lgbm.dart_draw", 5e9, 1e6),
               _span("lgbm.host_inputs", 0, 3e9, self_ns=1e9),
               _span("lgbm.enqueue", 3e9, 5e8),
               _span("lgbm.flush", 4e9, 1e9, dart_drops=40, dart_replayed=2,
                     dart_bank_rows=32, dart_bank_cap=64),
               _span("lgbm.flush", 7e9, 1e9, dart_drops=60,
                     dart_bank_rows=48, dart_bank_cap=64)]}
    monkeypatch.setattr(scopes_dart, "for_record", lambda record: red)
    record = {"trace": {"window_s": 100.0}, "window_tree_count": 16,
              "in_bag_rows": 819000000, "device_kind": "TPU v5 lite"}
    read = lambda name: scopes_dart.tree_seconds(record, name)
    assert read("dart_drop_tree_s") == 0.6 / 16
    assert read("dart_normalize_tree_s") == 0.9 / 16
    assert read("dart_replay_tree_s") == 0.25 / 16
    assert read("dart_bank_tree_s") == 0.125 / 16
    # a re-sort WITH the carry, and the carry alone as a part of it
    assert read("resort_tree_s.dart") == 6.0 / 16
    assert read("dart_carry_tree_s") == 2.0 / 16
    assert read("score_update_tree_s.dart") == 0.5 / 16
    assert read("hist_tree_s.dart") == 0.0
    assert read("dart_draw_tree_s") == 3e-3 / 16
    assert read("host_segment_tree_s.dart") == 1.5 / 16
    assert read("flush_tree_s.dart") == 2.0 / 16
    assert scopes_dart.flush_counters(record) == {
        "dart_drops": 100, "dart_replayed": 2, "dart_bank_rows": 48,
        "dart_bank_cap": 64}
    # 2 x 100 drops x 819e6 rows x 9 B at 819 GB/s = 1.8 s of 1.5 s: the
    # readers divide and do not clip
    from harness.cells import _module
    metric = lambda name: _module(os.path.join(
        dart_tiny.BENCH, "metrics", name + ".py")).read(record)
    assert metric("dart_surgery_roofline") == pytest.approx(120.0)
    assert metric("dart_drops_per_tree") == 100 / 16
    assert metric("dart_bank_fill_pct") == 75.0
    # a program that names none of DART's scopes, no lottery, no counters
    for k in [k for k in red["device_s"] if "dart" in k]:
        del red["device_s"][k]
    assert read("dart_drop_tree_s") is None
    assert read("dart_carry_tree_s") is None
    assert read("resort_tree_s.dart") == 4.0 / 16
    assert metric("dart_surgery_roofline") is None
    red["spans_in_window"] = [s for s in red["spans_in_window"]
                              if s.name != "lgbm.dart_draw"]
    assert read("dart_draw_tree_s") is None
    for s in red["spans_in_window"]:
        s.stats.clear()
    assert scopes_dart.flush_counters(record) is None
    assert metric("dart_drops_per_tree") is None
    assert metric("train_step_mfu.dart") is None
    red["has_scopes"] = False
    assert read("resort_tree_s.dart") is None
    monkeypatch.setattr(scopes_dart, "for_record", lambda record: None)
    assert read("flush_tree_s.dart") is None
    assert scopes_dart.flush_counters(record) is None
