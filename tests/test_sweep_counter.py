"""What the block-list sweeps of a tree cost, as the program counts it:
the `blocks_swept` and `grid_rows` stats of `lgbm.flush` (the tail of
_pack_tree's int row, from ops/grow.py's per-split occupied-block count)
against a numpy recount from the delivered trees and the row order.

The recount replays each tree's splits on the host's bin matrix, takes
the row orders the program's re-sorts made after trees 0, R, 2R, ...
(each one run a leaf in every shard under tree_learner=data), and counts
per sweep (the root's, then each split's smaller child) the row blocks
of each shard that hold a row of the swept leaf.  The kernel's grid runs
that many row steps, and one where a shard holds none: a run-time bound,
no compiled worst case (PERF.md section 6, PR 30).  Beside them stand
`feat_groups` and `block_matmuls`: what ONE row step costs in feature
groups (grid steps) and block-diagonal matmuls, static, from F alone
(ops/hist_pallas.py row_step; PR 32).  And `partition_blocks` (PR 34):
the row blocks the partition passes visited, which at each split are the
groups of PART_BLOCKS blocks of each shard that hold a row of the split
leaf (the last group of a shard holds what is left), where the two
passes over every id visited shards x blocks.  And `rows_swept`:
the in-bag rows of the leaves the sweeps targeted, the root's and each
split's smaller child's: over 8,192 the blocks they would fill, packed
(the benchmark's `sweep_block_excess` sets blocks_swept against it).
"""

import importlib.util
import os
import sys

import jax
import numpy as np
import pytest
from test_spans import _program_spans

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.ops.hist_pallas import (PALLAS_ROW_BLOCK, PART_BLOCKS,
                                          part_groups, row_step)
from lightgbm_tpu.utils import spans

LEAVES = 7
REORDER = 3
ROUNDS = 5
PARAMS = {"objective": "binary", "num_leaves": LEAVES, "min_data_in_leaf": 5,
          "verbose": -1, "device_type": "cpu", "hist_impl": "pallas",
          "hist_reorder_every": REORDER, "iter_batch": 2}


def _data(n, skew):
    """skew > 0 lets feature 0 drift along the file, so that a leaf's
    rows lie mostly in some shards and the shards' block counts differ."""
    rng = np.random.RandomState(5)
    x = rng.randn(n, 6).astype(np.float32)
    x[:, 0] += skew * np.linspace(-1.0, 1.0, n, dtype=np.float32)
    y = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(n) > 0
    return x, y.astype(np.float32)


def _train_traced(x, y, extra, trace_dir, monkeypatch):
    """-> (booster, its lgbm.flush stats, {t: (row order before, row order
    after, bag window)} of each re-sorting dispatch, t the tree it grew:
    a re-sorting dispatch grows one tree)."""
    resorts = {}

    def recorded(run):
        def dispatch(self, *args, **kwargs):
            t = len(self._models)
            before = (np.arange(self.n_pad) if self._row_order is None
                      else np.asarray(self._row_order))
            out = run(self, *args, **kwargs)
            if self._trees_since_reorder == 0:
                resorts[t] = (before, np.asarray(self._row_order),
                              self._bag_compact_rows()
                              if self._bag_arranged else 0)
            return out
        return dispatch

    for cls, name in ((gbdt_mod.GBDT, "_run_fused"),
                      (gbdt_mod.DART, "_run_fused_dart")):
        monkeypatch.setattr(cls, name, recorded(getattr(cls, name)))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are the host tracer's
    with jax.profiler.trace(trace_dir, profiler_options=options):
        booster = lgb.train({**PARAMS, **extra}, lgb.Dataset(x, label=y),
                            num_boost_round=ROUNDS)
    monkeypatch.undo()
    flushes = [s for name, s in _program_spans(trace_dir)
               if name == spans.FLUSH]
    return booster, flushes, resorts


def _leaves(tree, bins):
    """The leaf of every row of `bins` (file order), the tree's splits
    replayed on the host."""
    leaf = np.zeros(bins.shape[1], np.int64)
    for node in range(tree.num_leaves - 1):
        split = node                    # the left child keeps the split
        while split >= 0:               # leaf's index
            split = tree.left_child[split]
        leaf[(leaf == ~split) & (bins[tree.split_feature[node]]
                                 > tree.threshold_bin[node])] = node + 1
    return leaf


def _resorted(order, leaves, shards=1, window=0):
    """The re-sort of `order` (position -> file row) as the program should
    make it, from the host's replays alone: in each shard, and there in
    its first `window` positions under bag compaction, the rows stably
    sorted by the leaf of the tree the step grew, then by the leaves of
    the gbdt._RESORT_PREV trees before it (`leaves`, the latest first;
    zeros for a tree not grown)."""
    out = order.copy()
    per = len(order) // shards
    m = window if 0 < window < per else per
    for s in range(shards):
        mine = order[s * per:s * per + m]
        out[s * per:s * per + m] = mine[np.lexsort(
            [ids[mine] for ids in leaves[::-1]])]
    return out


def _history(trees, bins, t):
    """The leaves of tree t and of the gbdt._RESORT_PREV trees before
    it, the latest first."""
    return [_leaves(trees[i], bins) if i >= 0
            else np.zeros(bins.shape[1], np.int64)
            for i in range(t, t - gbdt_mod._RESORT_PREV - 1, -1)]


def _targeted_rows(tree):
    """The in-bag rows of the leaves a tree's sweeps targeted, from the
    delivered tree alone: the root's (every leaf's) and at each split the
    smaller child's, a child's rows being its subtree's leaf counts (a
    node's children are split after it)."""
    leaf_count = np.asarray(tree.leaf_count, np.int64)[:tree.num_leaves]
    node_rows = np.zeros(max(tree.num_leaves - 1, 0), np.int64)

    def rows(child):
        return node_rows[child] if child >= 0 else leaf_count[~child]

    total = int(leaf_count.sum())
    for node in range(tree.num_leaves - 2, -1, -1):
        a = rows(tree.left_child[node])
        b = rows(tree.right_child[node])
        node_rows[node] = a + b
        total += int(min(a, b))
    return total


def _recount(trees, bins, shards, resorts):
    """(occupied blocks, grid rows, blocks partitioned) of each shard,
    summed over the trees' sweeps and splits, the shard-local row order
    followed through the re-sorts (_resorted: by the leaf, then by the
    last trees' leaves inside it), each checked against the order the
    program's re-sort made (`resorts`); and the rows the sweeps
    targeted."""
    n = bins.shape[1]
    per = n // shards
    blocks = per // PALLAS_ROW_BLOCK
    order = np.arange(n)            # position -> file row, shard by shard
    occupied = np.zeros(shards, np.int64)
    grid = np.zeros(shards, np.int64)
    parted = np.zeros(shards, np.int64)
    rows = 0
    groups = part_groups(blocks)
    width = np.diff(np.minimum(np.arange(groups + 1) * PART_BLOCKS, blocks))

    def held_blocks(leaf_of_row, target):
        return (leaf_of_row[order] == target).reshape(
            shards, blocks, PALLAS_ROW_BLOCK).any(axis=2)

    def sweep(leaf_of_row, target):
        held = held_blocks(leaf_of_row, target)
        occupied[:] += held.sum(axis=1)
        grid[:] += np.maximum(held.sum(axis=1), 1)

    def partition(leaf_of_row, split):
        held = np.pad(held_blocks(leaf_of_row, split),
                      ((0, 0), (0, groups * PART_BLOCKS - blocks)))
        parted[:] += (held.reshape(shards, groups, PART_BLOCKS).any(axis=2)
                      * width).sum(axis=1)

    for t, tree in enumerate(trees):
        assert tree.num_leaves == LEAVES    # no step past the last split
        leaf = np.zeros(n, np.int64)
        sweep(leaf, 0)
        rows += n
        for node in range(tree.num_leaves - 1):
            split = node                    # the left child keeps the
            while split >= 0:               # split leaf's index
                split = tree.left_child[split]
            split, right = ~split, node + 1
            partition(leaf, split)
            go_right = ((leaf == split) & (bins[tree.split_feature[node]]
                                           > tree.threshold_bin[node]))
            leaf[go_right] = right
            n_left, n_right = np.sum(leaf == split), np.sum(leaf == right)
            sweep(leaf, split if n_left <= n_right else right)
            rows += min(n_left, n_right)
        if t % REORDER == 0:        # grown by the re-sorting step
            before, after, _ = resorts[t]
            assert np.array_equal(before, order)
            order = _resorted(order, _history(trees, bins, t), shards)
            assert np.array_equal(after, order)
    return occupied, grid, parted, rows


@pytest.mark.parametrize("shards,blocks", [(1, 6), (4, 3)],
                         ids=["serial", "data4"])
def test_flush_counts_the_blocks_the_sweeps_ran(shards, blocks, tmp_path,
                                               monkeypatch):
    x, y = _data(shards * blocks * PALLAS_ROW_BLOCK, skew=6.0 * (shards > 1))
    extra = ({"tree_learner": "data", "num_shards": shards}
             if shards > 1 else {})
    booster, flushes, resorts = _train_traced(x, y, extra, str(tmp_path),
                                              monkeypatch)
    gbdt = booster._gbdt
    assert gbdt.hist_ranged and gbdt._row_order is not None
    assert sum(s["trees"] for s in flushes) == ROUNDS == len(gbdt.models)
    assert sorted(resorts) == list(range(0, ROUNDS, REORDER))
    occupied, grid, parted, rows = _recount(
        gbdt.models, gbdt.train_data.bins, shards, resorts)
    assert sum(s["blocks_swept"] for s in flushes) == occupied.sum()
    # the rows those sweeps targeted: every leaf's, however it is sharded
    assert sum(s["rows_swept"] for s in flushes) == rows == sum(
        _targeted_rows(t) for t in gbdt.models)
    assert rows < occupied.sum() * PALLAS_ROW_BLOCK
    assert sum(s["grid_rows"] for s in flushes) == grid.sum()
    # the partition passes: the split leaf's groups of blocks, not every
    # block of every shard at every split
    assert sum(s["partition_blocks"] for s in flushes) == parted.sum()
    assert 0 < parted.sum() < ROUNDS * (LEAVES - 1) * shards * blocks
    # what a row step costs: 6 features are one group of 8, two matmuls,
    # the same on every shard (rows are sharded, features are not)
    assert {(s["feat_groups"], s["block_matmuls"]) for s in flushes} \
        == {row_step(x.shape[1])} == {(1, 2)}
    # clustered rows: far fewer than every block at every sweep
    assert occupied.sum() < ROUNDS * LEAVES * shards * blocks
    if shards == 1:
        # a leaf that is swept has rows: the grid ran its blocks, no more
        assert grid.sum() == occupied.sum()
    else:
        # the shards' counts differ (or the case would be the serial
        # one), and a shard that holds none of a leaf still runs one step
        assert len(set(occupied)) > 1
        assert (grid >= occupied).all() and grid.sum() > occupied.sum()
        # each shard's kernel runs to its OWN count, with no agreement on
        # a grid size before the sweep: the same trees as the serial
        # learner all the same
        serial = lgb.train(PARAMS, lgb.Dataset(x, label=y),
                           num_boost_round=ROUNDS)
        for t1, t2 in zip(serial._gbdt.models, gbdt.models):
            np.testing.assert_array_equal(t1.split_feature_real,
                                          t2.split_feature_real)
            np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
            np.testing.assert_array_equal(t1.leaf_count, t2.leaf_count)


@pytest.mark.parametrize("extra", [{"hist_impl": "xla"},
                                   {"hist_ordered": "off"}],
                         ids=["xla", "masked"])
def test_counters_read_zero_off_the_block_list(extra, tmp_path,
                                               monkeypatch):
    """The XLA sweep and the masked kernel list no blocks: their trees
    carry zeros behind the same packed row."""
    x, y = _data(PALLAS_ROW_BLOCK, skew=0.0)
    booster, flushes, _ = _train_traced(x, y, extra, str(tmp_path),
                                        monkeypatch)
    assert not booster._gbdt.hist_ranged
    assert sum(s["trees"] for s in flushes) == ROUNDS
    assert all(s["blocks_swept"] == s["grid_rows"] == s["partition_blocks"]
               == s["rows_swept"] == 0 for s in flushes)
    # the masked kernel runs the same feature grid (over every row
    # block); the XLA sweep runs no kernel and counts none
    want = (0, 0) if extra.get("hist_impl") == "xla" else (1, 2)
    assert {(s["feat_groups"], s["block_matmuls"]) for s in flushes} == {want}


@pytest.mark.parametrize("extra", [
    {"bagging_fraction": 0.5, "bagging_freq": 2, "bagging_seed": 3},
    {"boosting_type": "dart", "drop_rate": 0.5, "drop_seed": 4}],
    ids=["bagged", "dart"])
def test_rows_swept_are_the_swept_leaves_in_bag_rows(extra, tmp_path,
                                                     monkeypatch):
    """Under bagging (a compacted window) and under DART's re-sorting step
    the counter holds what the delivered trees say their sweeps targeted:
    a bagged root is its bag, not every row.  And each re-sort made the
    order the host's replays give (DART's earlier trees come from its
    tree bank, a bagged one sorts its window alone), which the leaf of
    the step's own tree alone would not give."""
    x, y = _data(4 * PALLAS_ROW_BLOCK, skew=0.0)
    booster, flushes, resorts = _train_traced(x, y, extra, str(tmp_path),
                                              monkeypatch)
    gbdt = booster._gbdt
    assert gbdt.hist_ranged and len(gbdt.models) == ROUNDS
    assert sorted(resorts) == list(range(0, ROUNDS, REORDER))
    bins = gbdt.train_data.bins
    for t, (before, after, window) in resorts.items():
        leaves = _history(gbdt.models, bins, t)
        assert np.array_equal(after, _resorted(before, leaves, 1, window))
        if t:
            assert not np.array_equal(
                after, _resorted(before, leaves[:1], 1, window))
    assert ("bagging_fraction" in extra) == (
        0 < resorts[REORDER][2] < len(x))
    assert sum(s["rows_swept"] for s in flushes) == sum(
        _targeted_rows(t) for t in gbdt.models)
    roots = [int(np.sum(t.leaf_count[:t.num_leaves])) for t in gbdt.models]
    if "bagging_fraction" in extra:
        assert all(r < len(x) for r in roots)
    else:
        assert roots == [len(x)] * ROUNDS


# -- the benchmark's reader of the two counters ------------------------------
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _reader(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_block_excess_reads_the_window_flushes(monkeypatch):
    """`sweep_block_excess` = blocks_swept x 8,192 / rows_swept summed over
    the traced window's flushes; nothing where no flush counts rows (the
    program before the counter, whose stats the trace reader leaves out
    at 0 as at absence), and nothing for an untraced run."""
    metric = _reader("sweep_block_excess")
    from harness import scopes

    def flush(**stats):
        return scopes.Span("lgbm.flush", 0.0, 1.0, stats)

    red = {"spans_in_window": [
        flush(trees=16, blocks_swept=2_000_000, rows_swept=2_500_000_000),
        scopes.Span("lgbm.enqueue", 0.0, 1.0, {"rows_swept": 7}),
        flush(trees=16, blocks_swept=1_000_000, rows_swept=500_000_000)]}
    monkeypatch.setattr(scopes, "for_record", lambda record: red)
    assert metric.read({"trace": {}}) == 3_000_000 * 8192 / 3_000_000_000
    red["spans_in_window"] = [flush(trees=16, blocks_swept=2_000_000)]
    assert metric.read({"trace": {}}) is None
    monkeypatch.setattr(scopes, "for_record", lambda record: None)
    assert metric.read({}) is None
