"""The re-sort's key (models/gbdt.py _resort_by_leaf): ONE uint32 a row,
the leaf id of the tree just grown in the high bits and below it the leaf
ids of the trees grown before it, the latest first, b bits each (b = the
bits of max_leaves - 1); the earlier trees' ids come from a replay of
their packed splits (_packed_leaf_ids), from DART's tree bank on the
device, the rows the fused step returned otherwise (GBDT._prev_trees).
Held here to numpy: the key's order at 2, 63, 64 and 255 leaves, ties in
their old order, the replay against the grow scan's own ids and against
the model's own leaf prediction, and _resort_by_leaf against a stable
lexicographic argsort and a take per array, to the bit, over the whole
rows and a compacted window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.objectives import Objective
from lightgbm_tpu.ops.grow import grow_tree
from lightgbm_tpu.ops.split import SplitParams

N = 6000


def _plain(gstate):
    return Objective.make_row_state_fn(None)(gstate)


def _ids(rng, leaves, n=N):
    """Leaf ids of this tree and the ones before it, in long runs of
    equal tuples, the extreme ids among them."""
    ids = rng.randint(0, leaves, (gbdt._RESORT_PREV + 1, n // 20 + 1))
    ids = np.repeat(ids, 20, axis=1)[:, :n].astype(np.int32)
    ids[:, :2] = [0, leaves - 1]
    return ids


@pytest.mark.parametrize("leaves", [2, 63, 64, 255])
def test_key_orders_rows_by_this_leaf_then_the_last_trees(leaves):
    rng = np.random.RandomState(leaves)
    ids = _ids(rng, leaves)
    key = np.asarray(gbdt._leaf_key([jnp.asarray(i) for i in ids], leaves))
    assert key.dtype == np.uint32
    b = (leaves - 1).bit_length()
    for k, want in enumerate(ids):      # each tree's id in its own bits
        shift = b * (len(ids) - 1 - k)
        np.testing.assert_array_equal((key >> shift) & ((1 << b) - 1), want)
    order = np.argsort(key, kind="stable")
    np.testing.assert_array_equal(order, np.lexsort(ids[::-1]))
    # rows of one leaf in every tree keep their old order
    tied = np.diff(key[order]) == 0
    assert tied.any() and (np.diff(order)[tied] > 0).all()


def test_key_keeps_the_trees_that_fit():
    """At 2,048 leaves (11 bits) two trees fit in 32 bits: the tree just
    grown and the one before it."""
    rng = np.random.RandomState(1)
    ids = [jnp.asarray(rng.randint(0, 2048, N).astype(np.int32))
           for _ in range(3)]
    key = np.asarray(gbdt._leaf_key(ids, 2048))
    np.testing.assert_array_equal(
        key, (np.asarray(ids[0]).astype(np.uint32) << 11)
        | np.asarray(ids[1]).astype(np.uint32))


def _grown(seed, leaves, n=N, f=5, b=32):
    """(bins, TreeArrays, the grow scan's leaf ids) of one tree."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, (f, n)).astype(np.uint8)
    grad = (bins[seed % f] / b - 0.5 + 0.3 * rng.randn(n)).astype(np.float32)
    tree, leaf = grow_tree(jnp.asarray(bins), jnp.asarray(grad),
                           jnp.ones(n, jnp.float32), jnp.ones(n, dtype=bool),
                           jnp.ones(f, dtype=bool), max_leaves=leaves,
                           max_bin=b, params=SplitParams(5, 1.0, 0.0, 0.0,
                                                         0.0))
    return bins, tree, np.asarray(leaf)


@pytest.mark.parametrize("leaves", [2, 7, 63])
def test_replay_of_a_packed_tree_gives_its_leaf_ids(leaves):
    bins, tree, leaf = _grown(leaves, leaves)
    assert len(np.unique(leaf)) == int(tree.num_leaves) > 1
    ints, _ = gbdt._pack_tree(tree)
    got = gbdt._packed_leaf_ids(ints, jnp.asarray(bins), leaves)
    np.testing.assert_array_equal(np.asarray(got), leaf)
    # no tree: every row in leaf 0
    none = gbdt._packed_leaf_ids(jnp.zeros_like(ints), jnp.asarray(bins),
                                 leaves)
    assert not np.asarray(none).any()


def test_host_packs_what_the_replay_reads(tmp_path):
    """The rows the re-sorting step is handed (GBDT._prev_trees): zeros
    before a tree is grown, then the device rows of the trees the fused
    step grew last, the latest first, which replay to the leaves the model
    itself predicts.  A checkpoint carries them: the resumed job's next
    re-sort orders its rows by the trees before the snapshot, and the job
    goes on to the bit as the one it continues."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops.hist_pallas import PALLAS_ROW_BLOCK
    n = 2 * PALLAS_ROW_BLOCK
    rng = np.random.RandomState(3)
    x = rng.randn(n, 5).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "device_type": "cpu", "hist_impl": "pallas",
              "hist_reorder_every": 3, "num_iterations": 7}
    ds = lgb.Dataset(x, label=y, params=params)

    def fresh():
        cfg = Config.from_params({k: str(v) for k, v in params.items()})
        obj = create_objective(cfg)
        obj.init(ds.inner.metadata, ds.inner.num_data)
        return gbdt.create_boosting(cfg, ds.inner, obj)

    a = fresh()
    assert a.hist_ranged
    assert not any(np.asarray(r).any() for r in a._prev_trees())
    for _ in range(6):          # trees 0-5: the re-sort of tree 6 is next
        a.train_one_iter(None, None, False)
    rows = a._prev_trees()
    assert len(rows) == gbdt._RESORT_PREV
    bins = jnp.asarray(a.train_data.bins)
    for k, row in enumerate(rows):
        assert isinstance(row, jax.Array) and row.dtype == jnp.int32
        got = np.asarray(gbdt._packed_leaf_ids(row, bins, 7))
        np.testing.assert_array_equal(
            got[:n], a.models[-1 - k].predict_leaf_index(x))
    ck = str(tmp_path / "ck.npz")
    a.save_checkpoint(ck)
    a.train_one_iter(None, None, False)
    assert a._trees_since_reorder == 0
    b = fresh()
    b.load_checkpoint(ck)
    for ra, rb in zip(rows, b._prev_trees()):
        np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
    b.train_one_iter(None, None, False)
    np.testing.assert_array_equal(np.asarray(a._row_order),
                                  np.asarray(b._row_order))
    assert [t.to_string() for t in a.models] == [
        t.to_string() for t in b.models]


def _take(a, rel, m):
    return np.concatenate([np.take(a[..., :m], rel, axis=-1), a[..., m:]],
                          axis=-1)


@pytest.mark.parametrize("window", [N, 4000], ids=["whole", "compacted"])
@pytest.mark.parametrize("leaves", [7, 63])
def test_resort_by_leaf_equals_argsort_and_take(leaves, window):
    """The step's re-sort: bins, the [1, N] scores, the bag and the row
    order, and the objective's state, by this tree's leaves and then the
    replayed leaves of the trees before it; under compaction the window
    [:m] sorts (the replay over the window's bins) and the tail stays."""
    bins, _, leaf = _grown(leaves + window, leaves)
    prev = [gbdt._pack_tree(_grown(s, leaves)[1])[0]
            for s in range(gbdt._RESORT_PREV)]
    rng = np.random.RandomState(window)
    bufs = [bins, rng.randn(1, N).astype(np.float32), rng.rand(N) > 0.3,
            rng.permutation(N).astype(np.int32)]
    gstate = (rng.randn(N).astype(np.float32), None)
    compact = window if window < N else 0
    got = jax.jit(lambda l, p, b, g: gbdt._resort_by_leaf(
        l, p, b, g, _plain, compact, leaves))(
        jnp.asarray(leaf), prev, [jnp.asarray(a) for a in bufs],
        (jnp.asarray(gstate[0]), None))
    earlier = [np.asarray(gbdt._packed_leaf_ids(
        p, jnp.asarray(bins[:, :window]), leaves)) for p in prev]
    assert all(len(np.unique(e)) > 1 for e in earlier)
    rel = np.lexsort(earlier[::-1] + [leaf[:window]])
    want = ([_take(a, rel, window) for a in bufs],
            (_take(gstate[0], rel, window), None))
    got_leaves = jax.tree_util.tree_leaves(got)
    want_leaves = jax.tree_util.tree_leaves(want)
    assert len(got_leaves) == len(want_leaves) == 5
    for g, w in zip(got_leaves, want_leaves):
        g = np.asarray(g)
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))
