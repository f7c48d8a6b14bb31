"""The re-sort moves a row's state once (models/gbdt.py _resort_rows):
every array that can be uint32 word rows (32-bit with one row a position;
narrow integers and bools of any height, the bin matrix among them) is
packed into ONE matrix that a single gather moves by the stable sort's
permutation; the rest follow it by a gather each.  Held here to the idiom it replaced,
`argsort(stable=True)` and a `take` per array, equal to the bit: the
helper alone over keys, payload dtypes, the window form and the
objective hooks, and the whole training step, serial and on four virtual
devices, binary and lambdarank, against the same step with the old idiom
put back (what is moved and what is computed equal to the bit) and
against the run that never re-sorts (the same trees; leaf values and
scores to the rounding of f32 sums taken in another row order).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.objectives import LambdarankNDCG, Objective

N = 5000


def _plain(gstate):
    return Objective.make_row_state_fn(None)(gstate)


def _oracle_resort_rows(keys, bufs, gstate, row_state):
    """What the step did before: one stable argsort a key, least
    significant first, then a take per array by the permutation."""
    rows, rebuild = row_state(gstate)
    arrays = list(bufs) + list(rows)
    n, m = arrays[0].shape[-1], keys[0].shape[0]
    rel = jnp.argsort(keys[-1], stable=True).astype(jnp.int32)
    for key in keys[-2::-1]:
        rel = jnp.take(rel, jnp.argsort(jnp.take(key, rel), stable=True))
    moved = [jnp.concatenate([jnp.take(a[..., :m], rel, axis=-1),
                              a[..., m:]], axis=-1) for a in arrays]
    rel = jnp.concatenate([rel, jnp.arange(m, n, dtype=jnp.int32)])
    return moved[:len(bufs)], rebuild(moved[len(bufs):], rel)


def _keys(kind, rng, m):
    if kind == "leaves63":      # long tie runs, as after an earlier re-sort
        return (jnp.asarray(np.repeat(rng.randint(0, 63, m // 50 + 1),
                                      50)[:m].astype(np.int32)),)
    if kind == "not_in_bag":    # _bag_arrange_body's key
        return (jnp.asarray(rng.rand(m) > 0.7),)
    if kind == "one_leaf":
        return (jnp.zeros(m, jnp.int32),)
    assert kind == "three_classes"      # the class-wise body's joint key
    return tuple(jnp.asarray(rng.randint(0, 7, m).astype(np.int32))
                 for _ in range(3))


PAYLOADS = {
    "f32": lambda rng: rng.randn(N).astype(np.float32),
    "int32": lambda rng: rng.permutation(N).astype(np.int32),
    "bool": lambda rng: rng.rand(N) > 0.5,
    "scores_row": lambda rng: rng.randn(1, N).astype(np.float32),
    "int8": lambda rng: rng.randint(-128, 128, N).astype(np.int8),
    "f16": lambda rng: rng.randn(N).astype(np.float16),
    "classwise": lambda rng: rng.randn(3, N).astype(np.float32),
    "bins": lambda rng: rng.randint(0, 255, (5, N)).astype(np.uint8),
    # the arrays that join the stacked matrix since PR 36: narrow
    # integers and bools with more than one row a position
    **{"bins_f%d" % f: (lambda rng, f=f: rng.randint(
        0, 256, (f, N)).astype(np.uint8)) for f in (1, 3, 4, 39, 220)},
    "bins_u16": lambda rng: rng.randint(0, 65536, (7, N)).astype(np.uint16),
    "int16_rows": lambda rng: rng.randint(-32768, 32768,
                                          (3, N)).astype(np.int16),
    "masks": lambda rng: rng.rand(3, N) > 0.5,
    # DART's leaf bank, high enough to need a second group of word rows
    "bank_u8": lambda rng: rng.randint(0, 256, (300, N)).astype(np.uint8),
    "bank_i32": lambda rng: rng.randint(0, 1000, (6, N)).astype(np.int32),
}
# the rows each payload fills in the stacked matrix; 0 = taken
WORD_ROWS = {"f32": 1, "int32": 1, "bool": 1, "scores_row": 1, "int8": 1,
             "f16": 0, "classwise": 0, "bins": 2, "bins_f1": 1,
             "bins_f3": 1, "bins_f4": 1, "bins_f39": 10, "bins_f220": 55,
             "bins_u16": 4, "int16_rows": 2, "masks": 1, "bank_u8": 75,
             "bank_i32": 0}


def _same(got, want):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        # bitwise: -0.0 and NaN payloads are moved, never computed on
        np.testing.assert_array_equal(np.asarray(g).view(np.uint8),
                                      np.asarray(w).view(np.uint8))


@pytest.mark.parametrize("window", [N, 3000])
@pytest.mark.parametrize("payload", sorted(PAYLOADS))
@pytest.mark.parametrize("key", ["leaves63", "not_in_bag", "one_leaf",
                                 "three_classes"])
def test_helper_equals_argsort_and_takes(key, payload, window):
    rng = np.random.RandomState(len(key) + len(payload))
    keys = _keys(key, rng, window)
    bufs = [jnp.asarray(PAYLOADS[payload](rng)),
            jnp.arange(N, dtype=jnp.int32)]
    gstate = (jnp.asarray(rng.randn(N).astype(np.float32)), None)
    got = jax.jit(lambda k, b, g: gbdt._resort_rows(k, b, g, _plain))(
        keys, bufs, gstate)
    want = _oracle_resort_rows(keys, bufs, gstate, _plain)
    _same(got, want)
    (moved, order), _ = got
    if window < N:          # the out-of-bag tail stays where it is
        _same([moved[..., window:], order[window:]],
              [bufs[0][..., window:], bufs[1][window:]])
    counts = gbdt._resort_counts(bufs, gstate, _plain)
    rows = WORD_ROWS[payload]
    assert counts == {"carried": 2 + (rows > 0), "taken": int(rows == 0),
                      "word_rows": 2 + rows}


@pytest.mark.parametrize("window", [N, 3000])
@pytest.mark.parametrize("payload", ["bins_f39", "bins_u16", "bool",
                                     "scores_row", "masks", "bank_u8",
                                     "int16_rows", "classwise"])
def test_blocks_of_columns_cover_the_window(payload, window, monkeypatch):
    """The stack is packed and unpacked a block of columns at a time,
    the last block pulled back to end at the window's end: five blocks
    of 1,024 columns over 5,000 rows (the last overlaps the fourth),
    three over a window of 3,000."""
    monkeypatch.setattr(gbdt, "_BLOCK_COLS", 1024)
    rng = np.random.RandomState(window + len(payload))
    keys = _keys("leaves63", rng, window)
    bufs = [jnp.asarray(PAYLOADS[payload](rng)),
            jnp.arange(N, dtype=jnp.int32)]
    gstate = (jnp.asarray(rng.randn(N).astype(np.float32)), None)
    _same(jax.jit(lambda k, b, g: gbdt._resort_rows(k, b, g, _plain))(
        keys, bufs, gstate), _oracle_resort_rows(keys, bufs, gstate, _plain))


def test_nan_and_negative_zero_payloads_are_moved_not_compared():
    special = np.array([np.nan, -0.0, 0.0, np.inf, -np.nan] * (N // 5),
                       np.float32)
    keys = _keys("leaves63", np.random.RandomState(1), N)
    _same(gbdt._resort_rows(keys, [jnp.asarray(special)], (), _plain),
          _oracle_resort_rows(keys, [jnp.asarray(special)], (), _plain))


@pytest.mark.parametrize("window", [N, 3000])
def test_lambdarank_state_remaps_its_positions(window):
    """row_slot moves with the rows; doc_idx holds row POSITIONS and is
    remapped through the inverse permutation, as before."""
    rng = np.random.RandomState(5)
    row_state = LambdarankNDCG.make_row_state_fn(None)
    di = jnp.asarray(rng.permutation(N).astype(np.int32).reshape(50, 10, 10))
    block = jnp.asarray(rng.randn(50, 10, 10).astype(np.float32))
    row_slot = jnp.asarray(rng.permutation(N).astype(np.int32))
    gstate = (di, block, block + 1, block[..., 0], block + 2, row_slot,
              jnp.arange(10.0))
    keys = _keys("leaves63", rng, window)
    scores = jnp.asarray(rng.randn(1, N).astype(np.float32))
    (moved,), new = gbdt._resort_rows(keys, [scores], gstate, row_state)
    _same(((moved,), new),
          _oracle_resort_rows(keys, [scores], gstate, row_state))
    # a document still finds its own score, and only di and row_slot moved
    _same(moved[0][new[0]], scores[0][di])
    _same(new[1:5] + new[6:], gstate[1:5] + gstate[6:])
    assert gbdt._resort_counts([scores], gstate, row_state) == {
        "carried": 2, "taken": 0, "word_rows": 2}


# -- the whole step --------------------------------------------------------
ROWS, TREES = 30000, 24


def _rows(objective):
    rng = np.random.RandomState(11)
    x = rng.randn(ROWS, 6).astype(np.float32)
    z = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.4 * rng.randn(ROWS)
    if objective == "binary":
        return x, (z > 0).astype(np.float32), None
    return (x, np.clip(np.round(z + 1.5), 0, 4).astype(np.float32),
            np.full(ROWS // 20, 20, np.int32))


def _train(objective, shards, **more):
    x, y, group = _rows(objective)
    params = {"objective": objective, "num_leaves": 15, "max_bin": 63,
              "learning_rate": 0.125,
              "min_data_in_leaf": 20, "metric": "", "verbose": -1,
              "device_type": "cpu", "hist_impl": "pallas",
              "hist_reorder_every": 4, "iter_batch": 2, **more}
    if objective == "lambdarank":
        params["rank_impl"] = "device"
    if shards > 1:
        params.update(tree_learner="data", num_shards=shards)
    return lgb.train(params, lgb.Dataset(x, label=y, group=group),
                     num_boost_round=TREES, verbose_eval=False)


def _forget_steps():
    gbdt._FUSED_STEPS.clear()
    jax.clear_caches()


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_training_grows_the_same_trees(objective, shards, monkeypatch):
    new = _train(objective, shards)
    g = new._gbdt
    order = np.asarray(g._row_order)
    assert np.array_equal(np.sort(order), np.arange(g.n_pad))
    assert not np.array_equal(order, np.arange(g.n_pad))

    # the old idiom in the helper's place: the same permutation, so the
    # same model, scores, row order and bins, bit for bit
    traced = []

    def oracle(*args):
        traced.append(len(args[1]))
        return _oracle_resort_rows(*args)

    _forget_steps()
    monkeypatch.setattr(gbdt, "_resort_rows", oracle)
    try:
        old = _train(objective, shards)
    finally:
        monkeypatch.undo()
        _forget_steps()
    assert traced == [4]        # bins, scores, bag, order: one re-sort step
    o = old._gbdt
    # what is MOVED is equal to the bit: the same permutation
    _same([g._row_order, g.bins_dev, g._gstate_override],
          [o._row_order, o.bins_dev, o._gstate_override])
    # and so is what is COMPUTED beside it: the rate is a power of two,
    # so a leaf value times the rate is exact and the score update rounds
    # once whether XLA:CPU contracts it into a multiply-add or not, which
    # it does by what else the fusion holds, and so differs between the
    # two steps (at lr 0.1 up to 7.4e-6 in the scores after 24 trees,
    # through every later tree's gradients)
    _same(g.scores, o.scores)
    _same_trees(g.models, o.models, atol=0)

    # against the run that never re-sorts: the same trees; the f32 sums
    # of histograms and leaves group their rows in another order there
    # (PARITY.md), so leaf values and scores agree to their rounding
    # (both within 9e-6 over the four cases)
    off = _train(objective, shards, hist_ordered="off")._gbdt
    assert off._row_order is None
    _same_trees(g.models, off.models, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g._training_score()),
                               np.asarray(off._training_score()),
                               rtol=0, atol=2e-5)


def _same_trees(ours, theirs, atol):
    assert len(ours) == len(theirs) == TREES
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(a.split_feature_real,
                                      b.split_feature_real)
        np.testing.assert_array_equal(a.threshold_bin, b.threshold_bin)
        np.testing.assert_array_equal(a.leaf_count, b.leaf_count)
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, rtol=0,
                                   atol=atol)
