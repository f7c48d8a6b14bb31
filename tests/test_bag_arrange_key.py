"""The bag arrangement's key (models/gbdt.py _bag_arrange_body): ONE uint32
a row, the out-of-bag bit at the top and below it the leaf ids of the
gbdt._RESORT_PREV trees grown last, the latest first, packed into 31 bits.
The in-bag rows fill the window first, as they always did, and lie there
in the order a re-sort by those trees would leave them, so the rows that
enter the bag at a redraw land in their leaves' runs.

Held here to numpy: the body against a stable lexicographic argsort of
(out of bag, the last tree's leaf, the one before's) and a take per
array, to the bit, at 7, 63 and 255 leaves; with no earlier tree the
plain in-bag-first partition, to the bit; the class-wise form on the
union bag alone; and whole training jobs (serial, tree_learner=data on
eight virtual devices, DART's banked path) whose every arrangement made
the order the host's replays of the delivered trees give, each shard
holding its own in-bag rows in its own window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_sweep_counter import _leaves

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.objectives import Objective
from lightgbm_tpu.ops.grow import grow_tree
from lightgbm_tpu.ops.split import SplitParams

N = 6000
F = 5
BINS = 32


def _plain(gstate):
    return Objective.make_row_state_fn(None)(gstate)


def _state(seed, leaves, trees=gbdt._RESORT_PREV):
    """(bins, per-row buffers, gstate, mask, the packed int rows of
    `trees` trees grown on the bins, the latest first, and the grow scan's
    own leaf ids of each)."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, BINS, (F, N)).astype(np.uint8)
    packed, ids = [], []
    for t in range(trees):
        grad = (bins[t % F] / BINS - 0.5
                + 0.3 * rng.randn(N)).astype(np.float32)
        tree, leaf = grow_tree(
            jnp.asarray(bins), jnp.asarray(grad), jnp.ones(N, jnp.float32),
            jnp.ones(N, dtype=bool), jnp.ones(F, dtype=bool),
            max_leaves=leaves, max_bin=BINS,
            params=SplitParams(5, 1.0, 0.0, 0.0, 0.0))
        assert len(np.unique(np.asarray(leaf))) > 1
        packed.append(gbdt._pack_tree(tree)[0])
        ids.append(np.asarray(leaf))
    mask = rng.rand(N) > 0.3
    bufs = [bins, rng.randn(1, N).astype(np.float32), mask,
            rng.permutation(N).astype(np.int32)]
    gstate = (rng.randn(N).astype(np.float32), None)
    return bufs, gstate, mask, tuple(packed), ids


def _arranged(bufs, gstate, prev, leaves, multi=False):
    arrange = jax.jit(gbdt._bag_arrange_body(_plain, multi, leaves))
    out = arrange(*[jnp.asarray(a) for a in bufs[:3]],
                  (jnp.asarray(gstate[0]), None), jnp.asarray(bufs[3]),
                  prev)
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(out)]


def _want(bufs, gstate, rel):
    """bins, scores, mask, the gstate's array, order: each taken by rel."""
    return [np.take(a, rel, axis=-1)
            for a in (bufs[0], bufs[1], bufs[2], gstate[0], bufs[3])]


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))


@pytest.mark.parametrize("leaves", [7, 63, 255])
def test_arrangement_orders_by_bag_then_the_last_trees(leaves):
    """The permutation is numpy's stable argsort of (out of bag, leaf of
    the last tree, leaf of the one before) over every row; the first
    rows are exactly the in-bag rows, and every array follows it."""
    bufs, gstate, mask, prev, ids = _state(leaves, leaves)
    got = _arranged(bufs, gstate, prev, leaves)
    rel = np.lexsort(ids[::-1] + [~mask])
    _same(got, _want(bufs, gstate, rel))
    w = int(mask.sum())
    assert got[2][:w].all() and not got[2][w:].any()
    assert sorted(got[4][:w]) == sorted(bufs[3][mask])
    # inside the bag the rows lie in the last tree's leaves' runs, which
    # the bag's bit alone would not give
    assert (np.diff(ids[0][rel[:w]]) >= 0).all()
    assert not np.array_equal(rel, np.argsort(~mask, kind="stable"))


@pytest.mark.parametrize("leaves", [7, 255])
def test_no_earlier_tree_is_the_in_bag_first_partition(leaves):
    """Rows of zeros (no tree grown yet) replay to leaf 0 everywhere: the
    plain stable in-bag-first partition, to the bit."""
    bufs, gstate, mask, prev, _ = _state(leaves + 1, leaves)
    zeros = tuple(jnp.zeros_like(p) for p in prev)
    got = _arranged(bufs, gstate, zeros, leaves)
    _same(got, _want(bufs, gstate, np.argsort(~mask, kind="stable")))


def test_class_wise_arrangement_keys_on_the_union_bag():
    """The class-wise form passes no trees: its rows sort by the union of
    the per-class masks alone, stably."""
    bufs, gstate, _, _, _ = _state(3, 7, trees=0)
    rng = np.random.RandomState(4)
    masks = rng.rand(3, N) > 0.8
    union = masks.any(axis=0)
    scores = rng.randn(3, N).astype(np.float32)
    got = _arranged([bufs[0], scores, masks, bufs[3]], gstate, (), 7,
                    multi=True)
    rel = np.argsort(~union, kind="stable")
    _same(got, _want([bufs[0], scores, masks, bufs[3]], gstate, rel))


def test_leaf_key_in_31_bits_leaves_the_top_bit_free():
    """At 2,048 leaves (11 bits) two ids fit in 32 bits and also in 31;
    at 65,536 (16 bits) two fit in 32 and one in 31: the bag's bit never
    lands on an id, and the re-sort's own 32-bit key is as it was."""
    rng = np.random.RandomState(2)
    for leaves, fit31 in ((2048, 2), (65536, 1)):
        ids = [jnp.asarray(rng.randint(0, leaves, N).astype(np.int32))
               for _ in range(2)]
        b = (leaves - 1).bit_length()
        key31 = np.asarray(gbdt._leaf_key(ids, leaves, bits=31))
        assert (key31 >> 31 == 0).all()
        want = np.zeros(N, np.uint32)
        for i in ids[:fit31]:
            want = (want << b) | np.asarray(i).astype(np.uint32)
        np.testing.assert_array_equal(key31, want)
        key32 = np.asarray(gbdt._leaf_key(ids, leaves))
        np.testing.assert_array_equal(
            key32, (np.asarray(ids[0]).astype(np.uint32) << b)
            | np.asarray(ids[1]).astype(np.uint32))


# -- whole jobs ----------------------------------------------------------------
JOB = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
       "min_data_in_leaf": 20, "metric": "", "verbose": -1,
       "bagging_fraction": 0.5, "bagging_freq": 2, "bagging_seed": 3,
       "bag_compact": "on"}
PATHS = {
    "serial": {},
    "data8": {"tree_learner": "data"},
    "dart": {"boosting_type": "dart", "drop_rate": 0.3},
}


def _arrangements(extra, rows, rounds, monkeypatch):
    """-> (booster, [(trees grown before it, the row order before, the
    file-order bag, the row order after)] of each arrangement)."""
    seen = []
    arrange = gbdt.GBDT._arrange_for_bag

    def recorded(self):
        before = (np.arange(self.n_pad) if self._row_order is None
                  else np.asarray(self._row_order))
        bag = np.zeros(self.n_pad, bool)
        bag[:self.num_data] = np.asarray(self.bag_masks[0])[:self.num_data]
        arrange(self)
        seen.append((len(self._models), before, bag,
                     np.asarray(self._row_order)))

    monkeypatch.setattr(gbdt.GBDT, "_arrange_for_bag", recorded)
    rng = np.random.RandomState(7)
    x = rng.randn(rows, 6).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
         + 0.3 * rng.randn(rows) > 0).astype(np.float32)
    booster = lgb.train({**JOB, **extra}, lgb.Dataset(x, label=y),
                        num_boost_round=rounds)
    monkeypatch.undo()
    return booster, seen


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_arrangement_of_a_job_keys_on_the_trees_before_it(
        path, monkeypatch):
    """Each arrangement of a bagged job (a redraw every second tree)
    made, in each shard, the stable sort of the shard's rows by (out of
    bag, leaf of the last tree, leaf of the one before) that the host's
    replays of the delivered trees give: none before the first tree (the
    in-bag-first partition), then the two grown last.  Each shard's
    in-bag rows fill the first positions of its own window."""
    rounds = 6
    booster, seen = _arrangements(PATHS[path], 4096, rounds, monkeypatch)
    g = booster._gbdt
    assert g._bag_window and g._bag_arranged and not g._bag_overflowed
    assert (path == "dart") == (getattr(g, "_bank", None) is not None)
    assert [t for t, *_ in seen] == list(range(0, rounds, 2))
    shards = g.grower.local_shard_count() if g._fused_sharded else 1
    assert shards == (8 if path == "data8" else 1)
    assert g.n_pad == 4096
    per = g.n_pad // shards
    bins = g.train_data.bins
    for t, before, bag, after in seen:
        ids = [_leaves(g.models[i], bins) if i >= 0
               else np.zeros(g.n_pad, np.int64)
               for i in range(t - 1, t - 1 - gbdt._RESORT_PREV, -1)]
        for s in range(shards):
            mine = before[s * per:(s + 1) * per]
            rel = np.lexsort([i[mine] for i in ids[::-1]] + [~bag[mine]])
            got = after[s * per:(s + 1) * per]
            np.testing.assert_array_equal(got, mine[rel])
            w = int(bag[mine].sum())
            assert w <= g._bag_window
            assert bag[got[:w]].all() and not bag[got[w:]].any()
            if t == 0:
                np.testing.assert_array_equal(
                    got, mine[np.argsort(~bag[mine], kind="stable")])
        if t:
            # the trees' leaves moved rows the bag's bit alone would not
            assert not np.array_equal(
                after, np.concatenate([
                    before[s * per:(s + 1) * per][np.argsort(
                        ~bag[before[s * per:(s + 1) * per]], kind="stable")]
                    for s in range(shards)]))
