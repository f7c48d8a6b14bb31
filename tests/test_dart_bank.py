"""DART's banked step on the ordered partition, and a leaf bank bounded by
the device's memory: the re-sorting step delivers the host-tree path's
model, a bank capped at three trees delivers the uncapped bank's, one
executable serves every drop count, and the spans and counters say what
was dropped, replayed and carried.  All on the CPU; `hist_impl=pallas` is
what makes the CPU take the chip's ordered path, `iter_batch` the chip's K.
"""

import glob
import os

import jax
import numpy as np
import pytest
from test_resort_rows import _forget_steps

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.models.gbdt import DART
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.utils import device, log, spans

ROUNDS = 12
N_PAD = 8192        # the 6,000 rows padded to the kernels' row block
COMMON = {"objective": "binary", "boosting_type": "dart", "num_leaves": 15,
          "max_bin": 63, "min_data_in_leaf": 20, "drop_rate": 0.3,
          "metric": "", "verbose": -1, "hist_impl": "pallas"}
# a re-sort at trees 0, 4, 8 and K-scans of 3 between them
ORDERED = {"hist_reorder_every": 4, "iter_batch": 3}
BAGGED = {"bagging_fraction": 0.5, "bagging_freq": 2, "bag_compact": "on"}


def _data(n=6000, f=5, seed=11):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    return x, (x[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float32)


def _train(extra, rounds=ROUNDS):
    x, y = _data()
    return lgb.train({**COMMON, **extra}, lgb.Dataset(x, label=y),
                     num_boost_round=rounds, verbose_eval=False)


def _host_path(extra, rounds=ROUNDS):
    """The same job on the host-tree path: the bank disabled up front."""
    x, y = _data()
    cfg = Config.from_params({str(k): str(v)
                              for k, v in {**COMMON, **extra}.items()})
    cfg.num_iterations = rounds
    inner = lgb.Dataset(x, label=y).inner
    objective = create_objective(cfg)
    objective.init(inner.metadata, inner.num_data)
    host = DART(cfg, inner, objective)
    host._bank_disabled = True
    host._flush_every = 1
    for _ in range(rounds):
        host.train_one_iter(None, None, False)
    assert host._bank is None
    return host


@pytest.mark.parametrize("sampling", [{}, BAGGED], ids=["plain", "bagged"])
def test_resorting_dart_delivers_the_host_tree_paths_model(sampling):
    """The banked step with the re-sort on (the leaf bank riding it, under
    bag compaction too) grows the trees the host-tree path grows: splits,
    thresholds and counts equal, leaf values to float32's rounding of the
    scores (the two paths add a tree's values in another order)."""
    got = _train({**ORDERED, **sampling})._gbdt
    assert got._bank is not None and got._row_order is not None
    want = _host_path({"hist_ordered": "off", **sampling})
    assert len(got.models) == len(want.models) == ROUNDS
    assert got.drop_history() == want.drop_history()
    for a, b in zip(got.models, want.models):
        np.testing.assert_array_equal(a.split_feature_real,
                                      b.split_feature_real)
        np.testing.assert_array_equal(a.threshold_bin, b.threshold_bin)
        np.testing.assert_array_equal(a.leaf_count, b.leaf_count)
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, rtol=1e-4,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(got._training_score()),
                               np.asarray(want._training_score()),
                               atol=2e-5)


def test_plan_is_the_plain_steps_one_resort_then_scans():
    """A DART job re-sorts at its first tree and then every
    `hist_reorder_every` trees, the re-sort dispatch alone, K-scans
    between: the plan the plain step has."""
    x, y = _data()
    b = lgb.Booster({**COMMON, "hist_reorder_every": 16, "iter_batch": 8},
                    lgb.Dataset(x, label=y))._gbdt
    made = []

    def run(k_iters=1):
        made.append(("resort" if b._reorder_now() else "dart", k_iters))
        if b._reorder_now():
            b._trees_since_reorder = 0
        else:
            b._trees_since_reorder += k_iters
        b._bank = b._bank or [None]

    b._run_fused_dart = run
    done = 0
    while done < 32:
        _, k = b.train_segment(32 - done, is_eval=False)
        done += k
    assert made == [("resort", 1), ("dart", 8), ("dart", 7)] * 2


def _limit_for(cap, n_pad, features, slots=DART._REPLAY_SLOTS):
    """The device memory limit under which DART._plan_bank makes a leaf
    bank of `cap` trees (+ the dummy row) beside `n_pad` rows of uint8
    bins: its own arithmetic, inverted."""
    live = n_pad * (features + 32)
    words = -(-features // 4) + 5
    step = n_pad * (4 * -(-words // 8) * 8 + 16)
    room = (cap + 1 + slots) * n_pad
    need = live + step + room
    return -(-need * 16 // 15) + 1


@pytest.fixture
def capped(monkeypatch):
    def cap(trees, n_pad, features):
        monkeypatch.setattr(device, "memory_limit_bytes",
                            lambda: _limit_for(trees, n_pad, features))
    return cap


@pytest.mark.parametrize("extra", [ORDERED, {**ORDERED, **BAGGED}],
                         ids=["plain", "bagged"])
def test_a_bank_of_three_trees_gives_the_uncapped_banks_model(extra, capped):
    """Twelve trees through a leaf bank that holds three (the bound comes
    from the memory limit the program reads): a dropped tree past the bank
    is replayed from its splits, and trees, scores and drop lists are the
    uncapped job's, to the bit."""
    whole = _train(extra)
    assert whole._gbdt._bank[2].shape[0] - 1 >= ROUNDS
    assert whole._gbdt._dart_counters()["dart_replayed"] == 0
    _forget_steps()
    assert whole._gbdt.n_pad == N_PAD
    capped(3, N_PAD, 5)
    x, y = _data()
    small = lgb.Booster({**COMMON, **extra, "num_iterations": ROUNDS},
                        lgb.Dataset(x, label=y))
    g = small._gbdt
    assert g._bank_plan == (4, DART._REPLAY_SLOTS)
    assert g._startup_stats() == {"bank_cap": 3, "bank_bytes": 4 * g.n_pad}
    done = 0
    while done < ROUNDS:
        _, k = g.train_segment(ROUNDS - done, is_eval=False)
        done += k
    replayed = g._dart_replayed     # no flush yet: 12 trees, one every 16
    assert g._bank[2].shape == (4, g.n_pad)
    assert replayed > 0
    assert g.drop_history() == whole._gbdt.drop_history()
    assert small.model_to_string() == whole.model_to_string()
    assert np.array_equal(np.asarray(g._training_score()),
                          np.asarray(whole._gbdt._training_score()))
    _forget_steps()


def test_a_bank_with_no_room_for_a_tree_stops_at_start_up(monkeypatch):
    x, y = _data()
    monkeypatch.setattr(device, "memory_limit_bytes", lambda: 300_000)
    with pytest.raises(log.LightGBMError, match="no room for one banked"):
        lgb.Booster(COMMON, lgb.Dataset(x, label=y))


def test_one_executable_whatever_an_iteration_drops():
    """At a drop rate of 0.7 the iterations of a 16-tree job drop from one
    tree to a dozen: every (kind, K) still has ONE executable, traced
    once (the drop count is a run-time bound of the scans)."""
    _forget_steps()
    b = _train({**ORDERED, "drop_rate": 0.7}, rounds=16)._gbdt
    counts = sorted({len(d) for d in b.drop_history()})
    assert counts[1] == 1 and counts[-1] >= 9, counts
    steps = {key: fn for key, fn in gbdt._FUSED_STEPS.items()
             if key[0] == "dart"}
    # the re-sorting step, K = 3
    assert sorted((key[-3], key[-1]) for key in steps) == [(False, 3),
                                                           (True, 1)]
    assert [fn._cache_size() for fn in steps.values()] == [1, 1]
    _forget_steps()


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name in spans.HOST_SPANS]


def test_spans_say_what_was_drawn_dropped_and_carried(tmp_path, capped):
    """A traced job through a bank of three trees: one `lgbm.dart_draw` an
    iteration with the lottery's count, the re-sorting dispatches with the
    bank among the carried arrays and nothing taken, the flushes with the
    drops, the replays and the bank's fill and bound."""
    _forget_steps()
    capped(3, N_PAD, 5)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        b = _train(ORDERED)._gbdt
        b._flush_pending()      # twelve trees: none was due yet
    found = _host_events(str(tmp_path))
    draws = [s for name, s in found if name == spans.DART_DRAW]
    assert [s["iter"] for s in draws] == list(range(ROUNDS))
    assert [s.get("k", 0) for s in draws] == [len(d)
                                              for d in b.drop_history()]
    resorts = [s for name, s in found
               if name == spans.ENQUEUE and s["kind"] == "resort"]
    assert len(resorts) == 3
    # bins, scores, bag, order, the objective's two arrays and the bank;
    # 5 words + 2 of bins + one word row of the bank's four byte rows
    assert all((s["carried"], s.get("taken", 0), s["word_rows"])
               == (7, 0, 8) for s in resorts), resorts
    assert {s["kind"] for name, s in found if name == spans.ENQUEUE} == {
        "resort", "dart"}
    flushes = [s for name, s in found if name == spans.FLUSH]
    assert sum(s.get("dart_drops", 0) for s in flushes) == sum(
        len(d) for d in b.drop_history())
    outside = sum(t >= 3 for d in b.drop_history() for t in d)
    assert outside > 0
    assert sum(s.get("dart_replayed", 0) for s in flushes) == outside
    assert flushes[-1]["dart_bank_rows"] == 3
    assert all(s["dart_bank_cap"] == 3 for s in flushes)
    _forget_steps()


def test_a_restored_job_rebuilds_the_bank_within_its_bound(tmp_path, capped):
    """A checkpoint taken past the bank's bound restores into a bank of
    the planned size (no [T, N] host buffer) and goes on to the model of
    the job that never stopped."""
    _forget_steps()
    x, y = _data()
    capped(3, N_PAD, 5)
    params = {**COMMON, **ORDERED, "num_iterations": ROUNDS}

    def job():
        return lgb.Booster(params, lgb.Dataset(x, label=y))

    whole = job()
    for _ in range(ROUNDS):
        whole._gbdt.train_one_iter(None, None, False)
    first = job()
    for _ in range(7):
        first._gbdt.train_one_iter(None, None, False)
    path = str(tmp_path / "dart.ckpt")
    first._gbdt.save_checkpoint(path)
    second = job()
    second._gbdt.load_checkpoint(path)
    g = second._gbdt
    assert g._bank[2].shape == (4, g.n_pad) and g._bank_count == 7
    assert g.drop_history() == whole._gbdt.drop_history()[:7]
    for _ in range(ROUNDS - 7):
        g.train_one_iter(None, None, False)
    assert g.drop_history() == whole._gbdt.drop_history()
    assert second.model_to_string() == whole.model_to_string()
    _forget_steps()


def test_the_start_up_line_and_record_say_the_banks_bound(capped,
                                                         monkeypatch):
    """B is in the booster's start-up record and in the job's one start-up
    info line, before the allocator has had a say.  (The process-wide
    records are emptied first: they keep the first spans.STARTUP_CAP, and
    an xdist worker may have built that many boosters before this test.)"""
    from lightgbm_tpu.utils import compile_cache
    monkeypatch.setattr(spans, "_records", [])
    monkeypatch.setattr(spans, "_dropped", 0)
    x, y = _data()
    capped(3, N_PAD, 5)
    lgb.Booster({**COMMON, "num_iterations": ROUNDS}, lgb.Dataset(x, label=y))
    record = [r for r in spans.startup_records()
              if r["name"] == spans.STARTUP_BOOSTER][-1]
    assert record["stats"]["bank_cap"] == 3
    assert record["stats"]["bank_bytes"] == 4 * N_PAD
    assert compile_cache.startup_line().endswith(
        "; DART leaf bank 3 trees, 0.00 GB")
