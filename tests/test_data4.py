"""The four-chip deployment of the benchmark (`criteo1tb-share64-data4`) at a
small size on the virtual CPU devices: the program on 4 shards against the
sharded reference, the sharded reference against the unsharded one, the
parallel row generator against the one it copies, and what this deployment
added to the program (block-wise placement, the shard-local inverse order,
the `shards` and `exchange_bytes` stats).
"""

import copy
import glob
import json
import os
import sys
import time
import types

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import faults_sharded  # noqa: E402
from drivers import train, train_sharded  # noqa: E402
from harness import (data, data_parallel, reference,  # noqa: E402
                     reference_sharded)
from lightgbm_tpu.models import gbdt  # noqa: E402
from lightgbm_tpu.parallel import mesh as mesh_mod  # noqa: E402
from lightgbm_tpu.utils import spans  # noqa: E402

SHARDS = 4
ROWS = 32768
TINY = {"num_leaves": 15, "min_data_in_leaf": 20,
        "min_sum_hessian_in_leaf": 1.0, "num_iterations": 40,
        "hist_reorder_every": 4}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell():
    """The real cell's configuration and limits, cut to 32k rows."""
    config = _load("configs", "criteo1tb-share64-data4.json")
    config["num_data"] = ROWS
    config["data"]["block_rows"] = 10000
    config["params"].update(TINY)
    limits = _load("workloads", "criteo64_train_4chip.json")["limits"]
    return types.SimpleNamespace(name="criteo64_train_4chip_small",
                                 config=config, chips=SHARDS, limits=limits)


def _forget_traces():
    """A planted `no_exchange` acts when a step is traced: nothing traced
    with the exchange may be reused, and nothing traced without it."""
    gbdt._FUSED_STEPS.clear()
    jax.clear_caches()


def _run(cell, tmp_path, **kw):
    _forget_traces()
    try:
        return train_sharded.run(cell, seed=2 ** 31 + 5, seconds=0.0,
                                 trace=False, t_process=time.time(),
                                 root=str(tmp_path), on_tpu=False, **kw)
    finally:
        faults_sharded.restore_exchange()
        _forget_traces()


# -- the program on 4 shards against the sharded reference -----------------
def test_configuration_is_the_one_chip_one_times_four():
    one = _load("configs", "criteo1tb-share64-binary.json")
    four = _load("configs", "criteo1tb-share64-data4.json")
    assert four["num_data"] == SHARDS * one["num_data"]
    assert four["data"] == one["data"]
    assert four["num_features"] == one["num_features"]
    extra = {"tree_learner": "data", "num_shards": SHARDS, "hist_agg": "psum"}
    assert four["params"] == dict(one["params"], **extra)
    assert four["assumed"][:len(one["assumed"])] == one["assumed"]
    assert four["reduced"] == ["num_data"]


def test_sound_run_is_correct(cell, tmp_path):
    record = _run(cell, tmp_path, control=True)
    assert record["correct"] is True, record["compared"]
    assert record["attempted"] == 4 and record["failed"] == 0
    assert record["shards"] == SHARDS and record["checked_trees"] == [4, 7]
    assert set(record["compared"]) == {"gain_loss", "leaf_update_gap",
                                       "leaf_count_gap", "score_gap",
                                       "trees_missing"}
    # the float8 control in the program's place, in the same run
    assert record["control_correct"] is False, record["control_compared"]


@pytest.mark.parametrize("fault,expect", [
    ("shard_left_out", "leaf_count_gap"), ("no_exchange", "leaf_count_gap")])
def test_planted_fault_is_not_correct(cell, tmp_path, fault, expect):
    record = _run(cell, tmp_path,
                  break_booster=faults_sharded.FAULTS[fault])
    assert record["correct"] is False, record["compared"]
    failing = {k for k, (v, lim) in record["compared"].items() if v > lim}
    assert expect in failing, record["compared"]
    if fault == "shard_left_out":   # a quarter of the rows is missing
        assert 0.2 < record["numbers"]["leaf_count_gap"] < 0.35
    else:       # the tree's counts are one shard's: three quarters missing
        assert record["numbers"]["leaf_count_gap"] > 0.6
        assert "score_gap" in failing


def test_ordered_sharded_path_compiles_nothing_in_the_window(cell, tmp_path):
    """The path the chip runs (Pallas kernels, here interpreted: re-sort
    step, then K-scan segments): the warm period's executables serve the
    window.  Before PR 27 the re-sort step compiled again at its second
    call, whose inputs were the step's own sharded outputs where the
    first call's sat on one device; the driver raises on that."""
    ordered = copy.copy(cell)
    ordered.config = copy.deepcopy(cell.config)
    ordered.config["params"].update(hist_impl="pallas", iter_batch=2)
    record = _run(ordered, tmp_path)
    assert record["correct"] is True, record["compared"]
    assert record["dispatches"] == 3        # re-sort, K=2, K=1
    assert record["failed"] == 0


def test_a_compile_inside_the_window_ends_the_run_there(cell, tmp_path):
    import jax.numpy as jnp

    def plant(booster):
        real = booster.train_segment
        calls = [0]

        def train_segment(max_iters, is_eval=True):
            calls[0] += 1
            if calls[0] > 4:      # past the four warm-up trees
                jax.jit(lambda x: x * 3 + calls[0])(jnp.ones(calls[0] + 7))
            return real(max_iters, is_eval)
        booster.train_segment = train_segment
    with pytest.raises(RuntimeError, match="inside the measured window"):
        _run(cell, tmp_path, break_booster=plant)


def test_shards_have_to_match_the_chips(cell, tmp_path):
    wrong = copy.copy(cell)
    wrong.chips = 2
    with pytest.raises(ValueError, match="num_shards"):
        _run(wrong, tmp_path)


# -- the sharded reference against the unsharded one -----------------------
@pytest.fixture(scope="module")
def grown(cell):
    """Rows and eight trees of a serial booster on them."""
    config = copy.deepcopy(cell.config)
    for k in ("tree_learner", "num_shards", "hist_agg"):
        del config["params"][k]
    rows = data.make_rows(config["data"], ROWS, 255, 11)
    booster = train.build_booster(config, rows, on_tpu=False)
    train.drive(booster, 8, lambda name: _Null())
    trees = [train.tree_dict(t) for t in booster.models]
    scores = np.asarray(booster._training_score(), np.float32).reshape(-1)
    return rows, config["params"], reference.Produced(trees, scores, 8)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_sharded_reference_equals_the_unsharded(grown):
    rows, params, produced = grown
    devices = jax.devices()[:SHARDS]
    checked = [3, 7]
    one = reference.compare(rows.bins, rows.label, params, produced, checked,
                            control=True)
    four = reference_sharded.compare(rows.bins, rows.label, params, produced,
                                     checked, devices, control=True)
    assert set(one) == set(four)
    for k in ("gain_loss", "leaf_update_gap", "leaf_count_gap", "score_gap",
              "trees_missing"):
        assert four[k] == pytest.approx(one[k], rel=1e-6, abs=1e-9), k
    for k in one:
        assert four[k] == pytest.approx(one[k], rel=1e-4, abs=1e-9), k
    # the histograms: four parts added in float64 against one part
    h4, s4 = reference_sharded.summed_histograms(
        rows.bins, rows.label, params, produced.trees, checked, True, devices)
    h1, s1 = reference_sharded.summed_histograms(
        rows.bins, rows.label, params, produced.trees, checked, True,
        devices[:1])
    np.testing.assert_array_equal(s4, s1)
    for t in checked:
        for a, b in zip(h4[t], h1[t]):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        # counts are sums of ones: exact in any grouping
        np.testing.assert_array_equal(h4[t][0][..., 2], h1[t][0][..., 2])
        assert h4[t][0][..., 2].sum() == ROWS * rows.bins.shape[0]


def test_shard_bounds_cover_the_rows():
    b = reference_sharded.shard_bounds(273342020, 4)
    assert b[0][0] == 0 and b[-1][1] == 273342020
    assert all(hi - lo == 68335505 for lo, hi in b)
    assert all(a[1] == c[0] for a, c in zip(b, b[1:]))


# -- the parallel generator -------------------------------------------------
@pytest.mark.parametrize("n,block", [(34567, 10000), (30000, 10000),
                                     (5000, 10000)])
def test_parallel_generator_makes_the_same_rows(cell, n, block):
    spec = dict(cell.config["data"], block_rows=block)
    a = data.make_rows(spec, n, 255, 2 ** 31 + 9)
    b = data_parallel.make_rows(spec, n, 255, 2 ** 31 + 9, threads=3)
    assert np.array_equal(a.bins, b.bins) and np.array_equal(a.label, b.label)
    assert all(np.array_equal(x, y)
               for x, y in zip(a.upper_bounds, b.upper_bounds))


# -- what the deployment added to the program ------------------------------
def test_row_blocks_are_placed_without_a_padded_copy():
    m = mesh_mod.make_mesh(SHARDS)
    arr = np.arange(3 * 37, dtype=np.uint8).reshape(3, 37)
    got = mesh_mod._put_row_blocks(arr, 40, 0, m,
                                   mesh_mod.P(None, mesh_mod.DATA_AXIS))
    assert got.shape == (3, 40)
    assert got.sharding.spec == mesh_mod.P(None, mesh_mod.DATA_AXIS)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.pad(arr, ((0, 0), (0, 3))))
    assert [s.data.shape for s in got.addressable_shards] == [(3, 10)] * 4
    flat = mesh_mod._put_row_blocks(np.ones(37, bool), 40, False, m,
                                    mesh_mod.P(mesh_mod.DATA_AXIS))
    assert np.asarray(flat).sum() == 37 and not np.asarray(flat)[37:].any()


def test_inverse_order_is_shard_local_and_right():
    m = mesh_mod.make_mesh(SHARDS)
    grower = mesh_mod.ShardedGrower(
        m, max_leaves=7, max_bin=16,
        params=gbdt.SplitParams(20, 1.0, 0.0, 0.0, 0.0))
    rng = np.random.default_rng(3)
    block = 64
    order = np.concatenate([s * block + rng.permutation(block)
                            for s in range(SHARDS)]).astype(np.int32)
    dev = grower.shard_rows(order, SHARDS * block)
    inv = np.asarray(grower.inverse_order(dev))
    np.testing.assert_array_equal(inv, np.argsort(order))
    vals = np.arange(SHARDS * block, dtype=np.float32)[None]
    sorted_vals = grower.permute_rows(
        grower.shard_rows(vals, SHARDS * block), dev)
    back = grower.permute_rows(sorted_vals, grower.inverse_order(dev))
    np.testing.assert_array_equal(np.asarray(back), vals)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name in spans.HOST_SPANS]


@pytest.mark.parametrize("learner,shards", [("data", SHARDS), ("serial", 1)])
def test_spans_carry_shards_and_exchange_bytes(cell, tmp_path, learner,
                                               shards):
    """`exchange_bytes` against a count by hand: one [F, B, 3] float32
    histogram a leaf of every flushed tree; 0 on the serial learner."""
    config = copy.deepcopy(cell.config)
    config["params"]["tree_learner"] = learner
    config["params"]["num_shards"] = shards
    rows = data.make_rows(config["data"], ROWS, 255, 5)
    booster = train.build_booster(config, rows, on_tpu=False)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        train.drive(booster, 8, lambda name: _Null())
        trees = booster.models
    found = _host_spans(str(tmp_path))
    enq = [s for n, s in found if n == spans.ENQUEUE]
    assert enq and all(s["shards"] == shards for s in enq)
    flushes = [s for n, s in found if n == spans.FLUSH]
    assert sum(s["trees"] for s in flushes) == 8
    leaves = sum(t.num_leaves for t in trees)
    f, b = rows.bins.shape[0], booster.max_bin
    by_hand = leaves * f * b * 3 * 4 if learner == "data" else 0
    assert sum(s["exchange_bytes"] for s in flushes) == by_hand
    assert b == max(len(u) for u in rows.upper_bounds)
