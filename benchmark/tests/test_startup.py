"""`harness/startup.py` and the nine start-up readers on hand-made records:
the cut at the window's start, the arithmetic of each metric, a program
that keeps no records, and every entry of BENCHMARK.json found by name."""

import json
import os

import pytest

import phase_table_startup
from harness import startup
from harness.cells import Cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = ("startup_first_dispatch_s", "startup_objective_s",
           "startup_booster_s", "startup_upload_s", "startup_unspanned_s",
           "startup_first_calls_s", "startup_trace_lower_s",
           "startup_other_compile_s", "startup_cache_misses")


def _span(name, t0, dur, parent=None, **stats):
    return {"name": name, "parent": parent, "t0": t0, "dur": dur,
            "stats": stats}


def _exe(fun, t0, trace_s, lower_s, backend_s, retrieval_s, hit,
         context="other", call=None):
    return {"fun": fun, "t0": t0, "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": backend_s, "retrieval_s": retrieval_s, "hit": hit,
            "context": context, "call": call}


def _record(setup_s=60.0):
    """A job whose window starts at 60 s: objective 2 s, booster 8 s of it
    upload 6 s, first dispatch at 31 s, three first calls before the
    window and one record of each kind after it."""
    spans = [
        _span("lgbm.startup_objective", 20.0, 2.0, rows=100, queries=4),
        _span("lgbm.startup_upload", 24.0, 6.0, "lgbm.startup_booster",
              bytes=1000, shards=1),
        _span("lgbm.startup_booster", 22.5, 8.0, rows=100),
        _span("lgbm.first_call", 31.0, 5.0, kind="resort", k=1, shards=1),
        _span("lgbm.first_call", 36.0, 3.0, kind="scan", k=8, shards=1),
        _span("lgbm.first_call", 39.0, 2.5, kind="scan", k=7, shards=1),
        _span("lgbm.first_call", 70.0, 9.0, kind="scan", k=3, shards=1),
        _span("lgbm.startup_objective", 90.0, 1.0, rows=1)]
    enq = "lgbm.enqueue"
    ledger = [
        _exe("jit(_pad)", 23.0, 0.01, 0.02, 0.25, 0.0, False,
             "lgbm.startup_booster"),
        _exe("jit(grad)", 20.5, 0.1, 0.2, 0.05, 0.5, True,
             "lgbm.startup_objective"),
        _exe("jit(step)", 31.0, 2.0, 1.0, 0.1, 0.4, True, enq,
             ["resort", 1, 1]),
        _exe("jit(batched)", 36.0, 1.5, 0.5, 0.1, 0.3, True, enq,
             ["scan", 8, 1]),
        _exe("jit(batched)", 39.0, 1.0, 0.5, 30.0, 0.0, False, enq,
             ["scan", 7, 1]),
        _exe("step", 45.0, 0.25, 0.0, 0.0, 0.0, None),
        _exe("jit(reference)", 80.0, 3.0, 3.0, 3.0, 0.0, False)]
    return {"measures": {"setup_s": setup_s, "train_tree_s": 1.0},
            "startup": {"spans": spans, "ledger": ledger,
                        "stamps": {"first_dispatch": 31.0,
                                   "first_tree": 58.0}}}


def _read(name, record):
    from harness.cells import _module
    return _module(os.path.join(ROOT, "benchmark", "metrics",
                                name + ".py")).read(record)


@pytest.mark.parametrize("name,want", [
    ("startup_first_dispatch_s", 31.0),
    ("startup_objective_s", 2.0),
    ("startup_booster_s", 8.0),
    ("startup_upload_s", 6.0),
    ("startup_unspanned_s", 21.0),
    ("startup_first_calls_s", 10.5),
    # every record before the window, the trace-only one among them
    ("startup_trace_lower_s", 0.03 + 0.3 + 3.0 + 2.0 + 1.5 + 0.25),
    # backend + load of the two records outside an lgbm.enqueue
    ("startup_other_compile_s", 0.25 + 0.55),
    ("startup_cache_misses", 2)])
def test_reader_on_hand_made_records(name, want):
    assert _read(name, _record()) == pytest.approx(want)


def test_the_cut_is_the_windows_start():
    """A window that starts earlier leaves the later first calls, their
    executables and the booster's spans out; the stamp stays."""
    early = _record(setup_s=37.0)
    assert _read("startup_first_calls_s", early) == pytest.approx(8.0)
    assert _read("startup_cache_misses", early) == 1
    assert _read("startup_trace_lower_s", early) == pytest.approx(
        0.03 + 0.3 + 3.0 + 2.0)
    assert _read("startup_first_dispatch_s", early) == 31.0
    none_yet = _record(setup_s=10.0)
    assert _read("startup_booster_s", none_yet) == 0.0
    assert _read("startup_cache_misses", none_yet) == 0


def test_the_three_parts_make_the_first_dispatch():
    r = _record()
    assert (_read("startup_objective_s", r) + _read("startup_booster_s", r)
            + _read("startup_unspanned_s", r)
            == pytest.approx(_read("startup_first_dispatch_s", r)))


def test_unspanned_is_never_negative():
    r = _record()
    r["startup"]["stamps"]["first_dispatch"] = 5.0   # a clock's 10 ms step
    assert _read("startup_unspanned_s", r) == 0.0


def test_a_record_without_a_process_age_is_before_nothing():
    r = _record()
    for rec in r["startup"]["spans"] + r["startup"]["ledger"]:
        rec["t0"] = None
    assert _read("startup_booster_s", r) == 0.0
    assert _read("startup_trace_lower_s", r) == 0.0
    r["startup"]["stamps"]["first_dispatch"] = None
    assert all(_read(name, r) is None for name in METRICS)


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_is_none(name, monkeypatch):
    """A program that keeps no records (the parent), and a record that
    measured nothing: None, and nothing raises."""
    monkeypatch.setattr(startup, "program_records", lambda: None)
    assert _read(name, {"measures": {"setup_s": 60.0}}) is None
    monkeypatch.undo()
    assert _read(name, {"answer": 1}) is None


def test_program_records_of_this_process():
    found = startup.program_records()
    assert set(found) == {"spans", "ledger", "stamps"}


def test_every_entry_has_its_reader_and_loads_through_the_cell(tiny_root):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    entries = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    assert sorted(entries) == sorted(METRICS)
    cells = [w["name"] for w in spec["workloads"]]
    for name, m in entries.items():
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
        assert (m["moves"], m["better"], m["workloads"]) == (
            "setup_s", "lower", cells)
        assert m["unit"] == ("count" if name == "startup_cache_misses"
                             else "s")
    for cell in cells:
        got = Cell(tiny_root, cell + "_tiny").per_layer(
            dict(_record(), peak_bytes=2 ** 30))
        assert set(METRICS) <= set(got)
        assert got["startup_unspanned_s"] == {"value": 21.0, "unit": "s"}
        assert got["startup_cache_misses"] == {"value": 2, "unit": "count"}


def test_self_time_leaves_the_children_out():
    spans = _record()["startup"]["spans"]
    own = startup.self_seconds(spans)
    assert own[2] == pytest.approx(2.0)      # the booster less its upload
    assert own[1] == pytest.approx(6.0) and own[0] == pytest.approx(2.0)


def test_the_table_from_the_records_a_run_leaves(tmp_path, capsys):
    r = _record()
    startup.leave_records(r["startup"], 60.0, str(tmp_path))
    import sys
    argv, sys.argv = sys.argv, ["phase_table_startup.py", str(tmp_path)]
    try:
        assert phase_table_startup.main() == 0
    finally:
        sys.argv = argv
    text = capsys.readouterr().out
    assert "first dispatch at    31.000 s" in text
    booster = [ln for ln in text.splitlines()
               if ln.startswith("lgbm.startup_booster")][0].split()
    assert booster[2:5] == ["22.500", "8.000", "2.000"]
    assert "enqueue scan k=7 x1" in text and "MISS" in text
    # what lies past the window is marked
    assert [ln for ln in text.splitlines()
            if "jit(reference)" in ln][0].rstrip().endswith("*MISS")
    assert "misses 2" in text
