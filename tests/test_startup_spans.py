"""A job's start as the program records it: the start-up spans of
lightgbm_tpu/utils/spans.py, the compile ledger of utils/compile_cache.py,
the first-call records of models/gbdt.py's _enqueue, and the one log line
built from them.  All on the CPU; what they read on the chip is
benchmark/phase_table_startup.py's business.
"""

import glob
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest
from test_resort_rows import _forget_steps

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.utils import compile_cache, spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUNDS = 12
PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbose": -1, "device_type": "cpu", "hist_impl": "pallas",
          "iter_batch": 4, "hist_reorder_every": 4}
DURATIONS = ("trace_s", "lower_s", "backend_s", "retrieval_s")


def _train(extra=None, n=4096, rounds=ROUNDS):
    rng = np.random.RandomState(5)
    x = rng.randn(n, 6).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] > 0).astype(np.float32)
    return lgb.train({**PARAMS, **(extra or {})}, lgb.Dataset(x, label=y),
                     num_boost_round=rounds)


@pytest.fixture
def fresh(monkeypatch):
    """The process-wide records emptied for one test (an xdist worker has
    run other files before this one), and every step forgotten so that
    the first training compiles."""
    monkeypatch.setattr(spans, "_records", [])
    monkeypatch.setattr(spans, "_dropped", 0)
    monkeypatch.setattr(spans, "_stamps", {})
    monkeypatch.setattr(compile_cache, "_ledger", [])
    monkeypatch.setattr(gbdt, "_FIRST_CALLS", set())
    monkeypatch.setattr(gbdt, "_DISPATCHES", 0)
    _forget_steps()
    yield
    _forget_steps()


def _named(name, records=None):
    rs = spans.startup_records() if records is None else records
    return [r for r in rs if r["name"] == name]


def test_booster_holds_its_upload_on_the_process_age_clock(fresh):
    booster = _train()
    g = booster._gbdt
    (dataset,) = _named(spans.STARTUP_DATASET)
    (objective,) = _named(spans.STARTUP_OBJECTIVE)
    (whole,) = _named(spans.STARTUP_BOOSTER)
    (upload,) = _named(spans.STARTUP_UPLOAD)
    assert dataset["stats"] == {"rows": 4096, "features": 6}
    assert objective["stats"] == {"rows": 4096}
    assert whole["stats"] == {"rows": 4096} and whole["parent"] is None
    assert upload["parent"] == spans.STARTUP_BOOSTER
    # the bin matrix's bytes plus the row state's, the scores
    assert upload["stats"] == {
        "shards": 1,
        "bytes": int(g.bins_dev.nbytes) + int(g.scores.nbytes)}
    assert whole["t0"] <= upload["t0"]
    assert upload["t0"] + upload["dur"] <= whole["t0"] + whole["dur"]
    # in the order a job runs them, before the device got work
    at = spans.stamps()
    assert (dataset["t0"] < objective["t0"] < whole["t0"]
            < at[spans.FIRST_DISPATCH] < at[spans.FIRST_TREE])
    assert 0 < dataset["t0"] < spans.process_age()
    assert set(r["name"] for r in spans.startup_records()) <= set(
        spans.STARTUP_SPANS)


def test_lambdarank_objective_counts_its_queries(fresh):
    rng = np.random.RandomState(2)
    x = rng.randn(512, 4).astype(np.float32)
    y = rng.randint(0, 3, 512).astype(np.float32)
    train = lgb.Dataset(x, label=y, group=np.full(32, 16, np.int32))
    lgb.train({**PARAMS, "objective": "lambdarank"}, train,
              num_boost_round=1)
    (objective,) = _named(spans.STARTUP_OBJECTIVE)
    assert objective["stats"] == {"rows": 512, "queries": 32}


class _Spy(gbdt._enqueue):
    keys: list = []

    def __init__(self, kind, k, shards=1, **stats):
        super().__init__(kind, k, shards, **stats)
        _Spy.keys.append(self.key)


def test_one_first_call_per_executable_and_none_the_second_time(
        fresh, monkeypatch):
    _Spy.keys = []
    monkeypatch.setattr(gbdt, "_enqueue", _Spy)
    _train()
    dispatched = list(_Spy.keys)
    firsts = _named(spans.FIRST_CALL)
    keys = [(r["stats"]["kind"], r["stats"]["k"], r["stats"]["shards"])
            for r in firsts]
    assert len(dispatched) > len(set(dispatched)) >= 2
    assert sorted(keys) == sorted(set(dispatched))
    enqueued = [r for r in compile_cache.ledger()
                if r["context"] == spans.ENQUEUE]
    assert sorted(tuple(r["call"]) for r in enqueued) == sorted(keys)
    for r in firsts:
        s = r["stats"]
        assert (s["executables"], s["hit"], s["again"]) == (1, 0, 0), s
        assert 0 < sum(s[f] for f in DURATIONS) <= r["dur"]
        (mine,) = [e for e in enqueued if tuple(e["call"]) == (
            s["kind"], s["k"], s["shards"])]
        assert [s[f] for f in DURATIONS] == [mine[f] for f in DURATIONS]
        assert mine["fun"].startswith("jit(") and mine["hit"] is False
        assert r["t0"] <= mine["t0"] <= r["t0"] + r["dur"]
    # the zero-recompile check, from the program's own record: a second
    # booster of the same shapes dispatches as much and compiles nothing
    ledger_before = len(compile_cache.ledger())
    _Spy.keys = []
    _train()
    assert _Spy.keys == dispatched
    assert len(_named(spans.FIRST_CALL)) == len(firsts)
    assert not [r for r in compile_cache.ledger()[ledger_before:]
                if r["context"] == spans.ENQUEUE]
    assert len(_named(spans.STARTUP_BOOSTER)) == 2


def test_a_second_executable_for_the_same_plan_reads_again(fresh):
    _train()
    firsts = _named(spans.FIRST_CALL)
    assert firsts and all(r["stats"]["again"] == 0 for r in firsts)
    _forget_steps()         # the same plan, new executables
    _train()
    again = _named(spans.FIRST_CALL)[len(firsts):]
    assert len(again) == len(firsts)
    assert all(r["stats"]["again"] == 1 for r in again)


def test_miss_then_hit_with_a_fresh_cache_directory(fresh, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = [getattr(jax.config, k) for k in keys]
    try:
        cc.reset_cache()
        for k, v in zip(keys, (str(tmp_path), 0, -1)):
            jax.config.update(k, v)
        plain = {"hist_impl": "xla"}
        _train(plain)
        cold = _named(spans.FIRST_CALL)
        assert cold and all(r["stats"]["hit"] == 0 for r in cold)
        assert compile_cache.totals()["misses"] > 0
        misses = [r for r in compile_cache.ledger()
                  if r["context"] == spans.ENQUEUE]
        assert misses and all(r["hit"] is False and r["retrieval_s"] == 0
                              for r in misses)
        _forget_steps()
        n = len(compile_cache.ledger())
        _train(plain)
        warm = _named(spans.FIRST_CALL)[len(cold):]
        assert len(warm) == len(cold)
        assert all(r["stats"]["hit"] == 1 for r in warm)
        hits = [r for r in compile_cache.ledger()[n:]
                if r["context"] == spans.ENQUEUE]
        assert hits and all(r["hit"] is True and r["retrieval_s"] > 0
                            for r in hits)
    finally:
        for k, v in zip(keys, before):
            jax.config.update(k, v)
        cc.reset_cache()


def test_the_ledger_names_the_context_an_executable_compiled_in(fresh):
    f = jax.jit(lambda x: x * 5 - 3)
    with spans.startup(spans.STARTUP_UPLOAD):
        f(np.ones(31, np.float32))
    f(np.ones(33, np.float32))
    mine = [r for r in compile_cache.ledger() if "lambda" in r["fun"]]
    assert [r["context"] for r in mine] == [spans.STARTUP_UPLOAD,
                                            compile_cache.OTHER]
    for r in mine:
        assert set(r) == set(compile_cache.LEDGER_FIELDS)
        assert r["hit"] is False and r["call"] is None
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
    t = compile_cache.totals()
    assert t["executables"] == t["hits"] + t["misses"] > 0


def test_the_cap_holds_and_counts_what_it_drops(fresh, monkeypatch):
    monkeypatch.setattr(spans, "STARTUP_CAP", 3)
    for i in range(5):
        with spans.startup(spans.STARTUP_DATASET, rows=i):
            pass
    assert [r["stats"]["rows"] for r in spans.startup_records()] == [0, 1, 2]
    assert spans.startup_dropped() == 2


def test_spans_nest_by_thread_and_take_late_stats(fresh):
    seen = {}

    def other_thread():
        with spans.startup(spans.STARTUP_OBJECTIVE):
            pass
        seen["contexts"] = list(spans.open_contexts())

    with spans.startup(spans.STARTUP_BOOSTER, rows=1) as stats:
        with spans.startup(spans.STARTUP_UPLOAD) as inner:
            inner["bytes"] = 7
        t = threading.Thread(target=other_thread)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        stats["more"] = 2
    upload, objective, booster = spans.startup_records()
    assert upload["parent"] == spans.STARTUP_BOOSTER
    assert upload["stats"] == {"bytes": 7}
    assert objective["parent"] is None and seen["contexts"] == []
    assert booster["stats"] == {"rows": 1, "more": 2}
    assert spans.open_contexts() == []


def test_process_age_is_none_safe_without_proc(fresh, monkeypatch):
    assert spans.process_age() > 0
    monkeypatch.setattr(spans, "_STAT_PATH", "/nonexistent/stat")
    monkeypatch.setattr(spans, "_started", None)
    assert spans.process_age() is None
    with spans.startup(spans.STARTUP_DATASET, rows=1) as stats:
        stats["features"] = 2
    (r,) = spans.startup_records()
    assert r["t0"] is None and r["dur"] >= 0
    assert spans.stamp(spans.FIRST_DISPATCH) and not spans.stamp(
        spans.FIRST_DISPATCH)
    assert spans.stamps() == {spans.FIRST_DISPATCH: None}
    assert "first dispatch at ? s" in compile_cache.startup_line()


def test_the_start_up_line_is_logged_once(fresh, capsys):
    _train({"verbose": 1})
    _train({"verbose": 1})
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "start-up: first dispatch at" in ln]
    assert len(lines) == 1
    assert lines[0].startswith("[LightGBM] [Info] start-up: ")
    for part in ("(objective ", "booster ", "of it upload ", " executables, "
                 "first calls ", " hits ", " misses; first tree on the host "
                 "at "):
        assert part in lines[0], lines[0]


def test_the_enqueue_span_says_which_call_was_the_first(fresh, tmp_path):
    """Under the profiler the start-up spans are annotations with their
    stats (the late ones too), and an lgbm.enqueue inside which an
    executable compiled carries first=1 and hit."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        _train()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name == spans.ENQUEUE or e.name in spans.STARTUP_SPANS]
    firsts = _named(spans.FIRST_CALL)
    enqueues = [s for name, s in events if name == spans.ENQUEUE]
    marked = [s for s in enqueues if s.get("first")]
    assert len(enqueues) > len(marked) == len(firsts) >= 2
    assert sorted((s["kind"], s["k"]) for s in marked) == sorted(
        (r["stats"]["kind"], r["stats"]["k"]) for r in firsts)
    by_name = dict(events)
    (upload,) = _named(spans.STARTUP_UPLOAD)
    assert by_name[spans.STARTUP_UPLOAD]["bytes"] == upload["stats"]["bytes"]
    assert by_name[spans.STARTUP_BOOSTER]["rows"] == 4096
    assert spans.FIRST_CALL not in by_name      # a record, no annotation


def test_the_registry_stays_jax_free():
    """A start-up span in a process that never imports jax (the native
    task=predict path) keeps its record and opens no annotation."""
    code = ("import sys\n"
            "from lightgbm_tpu.utils import compile_cache, spans\n"
            "with spans.startup(spans.STARTUP_DATASET, rows=3) as s:\n"
            "    s['features'] = 2\n"
            "(r,) = spans.startup_records()\n"
            "assert r['stats'] == {'rows': 3, 'features': 2}, r\n"
            "assert compile_cache.ledger() == []\n"
            "assert 'jax' not in sys.modules\n"
            "import lightgbm_tpu\n"
            "assert 'jax' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
