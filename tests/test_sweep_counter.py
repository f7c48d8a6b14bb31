"""What the block-list sweeps of a tree cost, as the program counts it:
the `blocks_swept` and `grid_rows` stats of `lgbm.flush` (the tail of
_pack_tree's int row, from ops/grow.py's per-split occupied-block count)
against a numpy recount from the delivered trees and the row order.

The recount replays each tree's splits on the host's bin matrix, keeps
the row order the ordered mode keeps (a stable re-sort by the leaves of
trees 0, R, 2R, ..., shard by shard under tree_learner=data), and counts
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
passes over every id visited shards x blocks.
"""

import jax
import numpy as np
import pytest
from test_spans import _program_spans

import lightgbm_tpu as lgb
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


def _train_traced(x, y, extra, trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are the host tracer's
    with jax.profiler.trace(trace_dir, profiler_options=options):
        booster = lgb.train({**PARAMS, **extra}, lgb.Dataset(x, label=y),
                            num_boost_round=ROUNDS)
    flushes = [s for name, s in _program_spans(trace_dir)
               if name == spans.FLUSH]
    return booster, flushes


def _recount(trees, bins, shards):
    """(occupied blocks, grid rows, blocks partitioned) of each shard,
    summed over the trees' sweeps and splits, the shard-local row order
    followed through the re-sorts."""
    n = bins.shape[1]
    per = n // shards
    blocks = per // PALLAS_ROW_BLOCK
    order = np.arange(n)            # position -> file row, shard by shard
    occupied = np.zeros(shards, np.int64)
    grid = np.zeros(shards, np.int64)
    parted = np.zeros(shards, np.int64)
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
        if t % REORDER == 0:        # grown by the re-sorting step
            for s in range(shards):
                part = order[s * per:(s + 1) * per]
                order[s * per:(s + 1) * per] = part[
                    np.argsort(leaf[part], kind="stable")]
    return occupied, grid, parted


@pytest.mark.parametrize("shards,blocks", [(1, 6), (4, 3)],
                         ids=["serial", "data4"])
def test_flush_counts_the_blocks_the_sweeps_ran(shards, blocks, tmp_path):
    x, y = _data(shards * blocks * PALLAS_ROW_BLOCK, skew=6.0 * (shards > 1))
    extra = ({"tree_learner": "data", "num_shards": shards}
             if shards > 1 else {})
    booster, flushes = _train_traced(x, y, extra, str(tmp_path))
    gbdt = booster._gbdt
    assert gbdt.hist_ranged and gbdt._row_order is not None
    assert sum(s["trees"] for s in flushes) == ROUNDS == len(gbdt.models)
    occupied, grid, parted = _recount(gbdt.models, gbdt.train_data.bins,
                                      shards)
    assert sum(s["blocks_swept"] for s in flushes) == occupied.sum()
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
def test_counters_read_zero_off_the_block_list(extra, tmp_path):
    """The XLA sweep and the masked kernel list no blocks: their trees
    carry zeros behind the same packed row."""
    x, y = _data(PALLAS_ROW_BLOCK, skew=0.0)
    booster, flushes = _train_traced(x, y, extra, str(tmp_path))
    assert not booster._gbdt.hist_ranged
    assert sum(s["trees"] for s in flushes) == ROUNDS
    assert all(s["blocks_swept"] == s["grid_rows"] == s["partition_blocks"]
               == 0 for s in flushes)
    # the masked kernel runs the same feature grid (over every row
    # block); the XLA sweep runs no kernel and counts none
    want = (0, 0) if extra.get("hist_impl") == "xla" else (1, 2)
    assert {(s["feat_groups"], s["block_matmuls"]) for s in flushes} == {want}
