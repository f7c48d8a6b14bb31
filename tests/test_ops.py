"""Unit tests for the device ops: histogram, best-split scan, tree grow,
prediction traversal — validated against straightforward numpy oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.grow import grow_tree
from lightgbm_tpu.ops.histogram import leaf_histogram, make_gvals
from lightgbm_tpu.ops.predict import predict_leaf_binned
from lightgbm_tpu.ops.split import SplitParams, find_best_split


def np_histogram(bins_t, gvals):
    f, n = bins_t.shape
    b = 256
    out = np.zeros((f, b, 3))
    for j in range(f):
        for r in range(n):
            out[j, bins_t[j, r]] += gvals[r]
    return out


def test_leaf_histogram_matches_oracle():
    rng = np.random.RandomState(42)
    n, f, b = 500, 7, 16
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float64)
    hess = rng.rand(n).astype(np.float64)
    mask = rng.rand(n) < 0.7
    gv = make_gvals(jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask),
                    jnp.float64)
    hist = np.asarray(leaf_histogram(jnp.asarray(bins_t), gv, max_bin=b))
    oracle = np_histogram(bins_t, np.asarray(gv))[:, :b]
    np.testing.assert_allclose(hist, oracle, rtol=1e-12)


def test_leaf_histogram_row_chunking():
    rng = np.random.RandomState(1)
    n, f, b = 333, 4, 8
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    gv = jnp.asarray(rng.randn(n, 3))
    full = leaf_histogram(jnp.asarray(bins_t), gv, max_bin=b)
    chunked = leaf_histogram(jnp.asarray(bins_t), gv, max_bin=b, row_chunk=100)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               rtol=1e-10)


def _scan_best_split_oracle(hist, count, sum_g, sum_h, params):
    """Literal transcription of FindBestThreshold
    (reference feature_histogram.hpp:112-170)."""
    f, b, _ = hist.shape
    eps = 1e-15
    best = (-np.inf, 0, b, None)  # gain, feature, threshold

    def gain_fn(g, h):
        a = abs(g)
        if a > params.lambda_l1:
            r = a - params.lambda_l1
            return r * r / (h + params.lambda_l2)
        return 0.0

    for fi in range(f):
        gain_shift = gain_fn(sum_g, sum_h)
        min_gain_shift = gain_shift + params.min_gain_to_split
        rg, rh, rc = 0.0, eps, 0
        fbest_gain, fbest_t = -np.inf, b
        for t in range(b - 1, 0, -1):
            rg += hist[fi, t, 0]
            rh += hist[fi, t, 1]
            rc += int(round(hist[fi, t, 2]))
            if rc < params.min_data_in_leaf or rh < params.min_sum_hessian_in_leaf:
                continue
            lc = count - rc
            if lc < params.min_data_in_leaf:
                break
            lh = sum_h - rh
            if lh < params.min_sum_hessian_in_leaf:
                break
            lg = sum_g - rg
            cur = gain_fn(lg, lh) + gain_fn(rg, rh)
            if cur < min_gain_shift:
                continue
            if cur > fbest_gain:
                fbest_gain, fbest_t = cur, t - 1
        if fbest_gain - gain_shift > best[0]:
            best = (fbest_gain - gain_shift, fi, fbest_t, None)
    return best


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_find_best_split_matches_scalar_scan(seed):
    rng = np.random.RandomState(seed)
    f, b = 5, 12
    n = 400
    bins = rng.randint(0, b, size=(f, n))
    grad = rng.randn(n)
    hess = np.abs(rng.rand(n)) + 0.1
    hist = np.zeros((f, b, 3))
    for fi in range(f):
        for r in range(n):
            hist[fi, bins[fi, r]] += (grad[r], hess[r], 1.0)
    sum_g, sum_h = grad.sum(), hess.sum()
    params = SplitParams(min_data_in_leaf=20, min_sum_hessian_in_leaf=1.0,
                         lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0)
    got = jax.tree_util.tree_map(
        np.asarray,
        find_best_split(jnp.asarray(hist), jnp.int32(n),
                        jnp.float64(sum_g), jnp.float64(sum_h),
                        jnp.ones(f, dtype=bool), params))
    want_gain, want_f, want_t, _ = _scan_best_split_oracle(
        hist, n, sum_g, sum_h, params)
    assert int(got.feature) == want_f
    assert int(got.threshold) == want_t
    np.testing.assert_allclose(float(got.gain), want_gain, rtol=1e-9)


def test_find_best_split_l1_l2():
    rng = np.random.RandomState(7)
    f, b, n = 3, 10, 300
    bins = rng.randint(0, b, size=(f, n))
    grad = rng.randn(n)
    hess = np.abs(rng.rand(n)) + 0.1
    hist = np.zeros((f, b, 3))
    for fi in range(f):
        for r in range(n):
            hist[fi, bins[fi, r]] += (grad[r], hess[r], 1.0)
    params = SplitParams(min_data_in_leaf=10, min_sum_hessian_in_leaf=0.5,
                         lambda_l1=0.3, lambda_l2=1.5, min_gain_to_split=0.1)
    got = find_best_split(jnp.asarray(hist), jnp.int32(n),
                          jnp.float64(grad.sum()), jnp.float64(hess.sum()),
                          jnp.ones(f, dtype=bool), params)
    want = _scan_best_split_oracle(hist, n, grad.sum(), hess.sum(), params)
    assert int(got.feature) == want[1]
    assert int(got.threshold) == want[2]
    np.testing.assert_allclose(float(got.gain), want[0], rtol=1e-9)


def test_feature_mask_respected():
    rng = np.random.RandomState(3)
    f, b, n = 4, 8, 200
    hist = np.abs(rng.randn(f, b, 3))
    hist[:, :, 2] = 10.0
    count = int(hist[0, :, 2].sum())
    mask = np.array([False, True, False, True])
    params = SplitParams(1, 0.0, 0.0, 0.0, 0.0)
    got = find_best_split(jnp.asarray(hist), jnp.int32(count),
                          jnp.float64(hist[0, :, 0].sum()),
                          jnp.float64(hist[0, :, 1].sum()),
                          jnp.asarray(mask), params)
    assert int(got.feature) in (1, 3)


def _grow_simple(n=800, f=3, b=8, max_leaves=8, seed=0, **kw):
    rng = np.random.RandomState(seed)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    # target correlated with feature 0 bins
    grad = (bins_t[0] / b - 0.5 + 0.1 * rng.randn(n)).astype(np.float64)
    hess = np.ones(n)
    params = SplitParams(min_data_in_leaf=10, min_sum_hessian_in_leaf=1.0,
                         lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0)
    tree, leaf_id = grow_tree(
        jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, dtype=bool), jnp.ones(f, dtype=bool),
        max_leaves=max_leaves, max_bin=b, params=params, **kw)
    return bins_t, grad, tree, np.asarray(leaf_id)


def test_grow_tree_basic():
    bins_t, grad, tree, leaf_id = _grow_simple()
    nl = int(tree.num_leaves)
    assert 2 <= nl <= 8
    # leaf_id consistent with tree traversal
    walked = np.asarray(predict_leaf_binned(
        tree.split_feature, tree.threshold_bin, tree.left_child,
        tree.right_child, jnp.asarray(bins_t)))
    np.testing.assert_array_equal(leaf_id, walked)
    # leaf counts match partition
    counts = np.bincount(leaf_id, minlength=nl)
    np.testing.assert_array_equal(counts[:nl],
                                  np.asarray(tree.leaf_count)[:nl])
    # root split should be on the informative feature
    assert int(np.asarray(tree.split_feature)[0]) == 0


def test_grow_tree_reduces_loss():
    bins_t, grad, tree, leaf_id = _grow_simple()
    nl = int(tree.num_leaves)
    leaf_vals = np.asarray(tree.leaf_value)
    # with hess=1, leaf value = -mean(grad in leaf); applying it must
    # reduce squared gradient norm
    new = grad + leaf_vals[leaf_id]
    assert (new ** 2).sum() < (grad ** 2).sum() * 0.9


def test_grow_tree_max_depth():
    _, _, tree, _ = _grow_simple(max_depth=2)
    nl = int(tree.num_leaves)
    assert nl <= 4  # depth-2 tree has at most 4 leaves
    assert np.asarray(tree.leaf_depth)[:nl].max() <= 3  # root depth is 1


def test_grow_tree_min_data_stops():
    # min_data_in_leaf = n/2 + 1 makes any split invalid
    n = 100
    rng = np.random.RandomState(0)
    bins_t = rng.randint(0, 4, size=(2, n)).astype(np.uint8)
    params = SplitParams(min_data_in_leaf=51, min_sum_hessian_in_leaf=0.0,
                         lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0)
    tree, _ = grow_tree(jnp.asarray(bins_t),
                        jnp.asarray(rng.randn(n)), jnp.ones(n),
                        jnp.ones(n, dtype=bool), jnp.ones(2, dtype=bool),
                        max_leaves=8, max_bin=4, params=params)
    assert int(tree.num_leaves) == 1


def test_grow_tree_bagging_mask():
    # rows outside the bag must not influence counts
    n, f, b = 400, 2, 8
    rng = np.random.RandomState(5)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    grad = rng.randn(n)
    bag = np.zeros(n, dtype=bool)
    bag[: n // 2] = True
    params = SplitParams(5, 0.0, 0.0, 0.0, 0.0)
    tree, leaf_id = grow_tree(jnp.asarray(bins_t), jnp.asarray(grad),
                              jnp.ones(n), jnp.asarray(bag),
                              jnp.ones(f, dtype=bool),
                              max_leaves=4, max_bin=b, params=params)
    nl = int(tree.num_leaves)
    # leaf_count counts only bagged rows
    bag_counts = np.bincount(np.asarray(leaf_id)[bag], minlength=nl)
    np.testing.assert_array_equal(bag_counts[:nl],
                                  np.asarray(tree.leaf_count)[:nl])
    assert int(np.asarray(tree.leaf_count)[:nl].sum()) == n // 2


# ---- bounded histogram pool (hist_slots; reference HistogramPool role,
# feature_histogram.hpp:275-398) --------------------------------------

def _pool_workload(n=5000, f=12, b=64, seed=0):
    rng = np.random.RandomState(seed)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    y = (rng.randn(n) + bins_t[0] / 16.0 > 2).astype(np.float64)
    grad = 0.5 - y
    hess = np.full(n, 0.25)
    return bins_t, grad, hess


@pytest.mark.parametrize("slots", [2, 3, 8, 31])
def test_hist_pool_tree_identity(slots):
    """A bounded pool (any size >= 2) must grow the IDENTICAL tree to the
    dense unbounded default: eviction only trades memory for parent-
    histogram recomputes, never changes the arithmetic outcome (f64)."""
    n, f, b, L = 5000, 12, 64, 31
    bins_t, grad, hess = _pool_workload(n, f, b)
    params = SplitParams(20, 1e-3, 0.0, 0.0, 0.0)
    args = (jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(n, dtype=bool), jnp.ones(f, dtype=bool))
    kw = dict(max_leaves=L, max_bin=b, params=params)
    dense_tree, dense_leaf = grow_tree(*args, **kw)
    pool_tree, pool_leaf = grow_tree(*args, **kw, hist_slots=slots)
    assert int(dense_tree.num_leaves) == L
    for a, b_ in zip(dense_tree, pool_tree):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    np.testing.assert_array_equal(np.asarray(dense_leaf),
                                  np.asarray(pool_leaf))


@pytest.mark.slow
def test_hist_pool_wide_shape():
    """The VERDICT-r1 scale gap: num_leaves=255, F=2000, max_bin=256.
    Dense histograms would need (255+1) x 2000 x 256 x 3 x 4B = 1.5 GB;
    a 64-slot pool holds 381 MB and must still grow a valid deep tree.
    (Rows are few — the claim under test is the histogram working-set
    bound, which is independent of N.)"""
    n, f, b, L, slots = 2048, 2000, 256, 255, 64
    rng = np.random.RandomState(1)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    params = SplitParams(1, 0.0, 0.0, 0.0, 0.0)
    tree, leaf_id = grow_tree(
        jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, dtype=bool), jnp.ones(f, dtype=bool),
        max_leaves=L, max_bin=b, params=params, hist_slots=slots)
    nl = int(tree.num_leaves)
    assert nl > L // 2   # pure-noise gradients split deep
    # structural sanity of the deep tree: leaf counts partition the rows
    counts = np.bincount(np.asarray(leaf_id), minlength=nl)
    np.testing.assert_array_equal(counts[:nl],
                                  np.asarray(tree.leaf_count)[:nl])


def test_split_hi_lo_total_order():
    """The uint32-pair key must reproduce the f64 <= compare EXACTLY for
    extremes the old Dekker float split collapsed: +-1e308 (the parser's
    inf mapping), sub-f32-range magnitudes, signed zeros, NaN."""
    from lightgbm_tpu.ops.predict import split_hi_lo

    vals = np.array([-np.inf, -1e308, -5e307, -3.4e38, -1.857, -1e-300,
                     -0.0, 0.0, 1e-300, 2e-300, 1.457, 1.4569999999999999,
                     3.4e38, 5e307, 1e308, np.inf])
    h, lo = split_hi_lo(vals)
    for i, a in enumerate(vals):
        for j, b in enumerate(vals):
            lex = bool((h[i] < h[j]) | ((h[i] == h[j]) & (lo[i] <= lo[j])))
            assert lex == (a <= b), (a, b)
    # NaN routes right: value <= threshold false against every threshold
    nh, nl = split_hi_lo(np.array([np.nan]))
    for j in range(len(vals)):
        assert not bool((nh[0] < h[j]) | ((nh[0] == h[j]) & (nl[0] <= lo[j])))


def test_predict_extreme_values_match_host_traversal():
    """Device stacked traversal == per-tree host numpy traversal on data
    containing +-1e308 / tiny / NaN-free extremes (predictor parity for
    the inf -> +-1e308 Atof mapping)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.binning import find_bins
    from lightgbm_tpu.io.dataset import Dataset, Metadata
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(11)
    n, f = 600, 6
    x = rng.randn(n, f)
    x[rng.rand(n) < 0.05] *= 1e305           # huge magnitudes
    x[rng.rand(n) < 0.05] *= 1e-300          # tiny magnitudes
    y = (x[:, 0] > 0).astype(np.float64)
    cfg = Config.from_params({"objective": "binary", "num_trees": "5",
                              "num_leaves": "7", "min_data_in_leaf": "5"})
    mappers = find_bins(x, n, cfg.max_bin)
    bins = np.stack([m.value_to_bin(x[:, j]).astype(np.uint8)
                     for j, m in enumerate(mappers)])
    ds = Dataset(bins=bins, bin_mappers=mappers,
                 used_feature_map=np.arange(f, dtype=np.int32),
                 real_feature_index=np.arange(f, dtype=np.int32),
                 num_total_features=f,
                 feature_names=["Column_%d" % i for i in range(f)],
                 metadata=Metadata(label=y))
    obj = create_objective(cfg)
    obj.init(ds.metadata, n)
    booster = create_boosting(cfg, ds, obj)
    for _ in range(5):
        booster.train_one_iter(None, None, False)

    xt = rng.randn(200, f)
    xt[::7] *= 1e305
    xt[::11] *= 1e-300
    got = booster.predict_raw(xt)
    want = np.zeros_like(got)
    for i, tree in enumerate(booster.models[:booster.num_used_model]):
        want[i % booster.num_class] += tree.predict(xt)
    np.testing.assert_array_equal(got, want)
    # narrow matrix: missing trailing features read as 0.0, not clamped
    narrow = xt[:, :3]
    wide = np.pad(narrow, ((0, 0), (0, f - 3)))
    np.testing.assert_array_equal(booster.predict_raw(narrow),
                                  booster.predict_raw(wide))


def test_matmul_predictor_matches_descent():
    """The gather-free matmul predictor (selection matmul + path-score
    argmax over host rank codes) must agree with the while-loop descent
    AND the per-tree host traversal exactly, including huge/tiny values
    and the padded dummy trees."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.binning import find_bins
    from lightgbm_tpu.io.dataset import Dataset, Metadata
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops.predict import (predict_leaf_matmul,
                                          rank_encode, split_hi_lo)

    rng = np.random.RandomState(4)
    n, f = 800, 7
    x = rng.randn(n, f)
    x[rng.rand(n) < 0.03] *= 1e305
    y = (x[:, 0] > 0).astype(np.float64)
    cfg = Config.from_params({"objective": "binary", "num_leaves": "9",
                              "min_data_in_leaf": "5"})
    mappers = find_bins(x, n, cfg.max_bin)
    bins = np.stack([m.value_to_bin(x[:, j]).astype(np.uint8)
                     for j, m in enumerate(mappers)])
    ds = Dataset(bins=bins, bin_mappers=mappers,
                 used_feature_map=np.arange(f, dtype=np.int32),
                 real_feature_index=np.arange(f, dtype=np.int32),
                 num_total_features=f,
                 feature_names=["c%d" % i for i in range(f)],
                 metadata=Metadata(label=y))
    obj = create_objective(cfg)
    obj.init(ds.metadata, n)
    b = create_boosting(cfg, ds, obj)
    for _ in range(11):     # 11 trees -> padded to 16 with dummies
        b.train_one_iter(None, None, False)
    _ = b.models

    xt = rng.randn(300, f)
    xt[::9] *= 1e305
    want = np.stack([t.predict_leaf_index(xt) for t in b.models[:11]],
                    axis=1)
    mm = b._matmul_cached(b._stacked_trees(11))
    assert mm is not None
    tables, mm_dev = mm
    xh, xl = split_hi_lo(np.asarray(xt, dtype=np.float64))
    code = rank_encode(xh, xl, tables)
    got = np.asarray(predict_leaf_matmul(
        *mm_dev, jnp.asarray(code),
        tree_block=b.PREDICT_TREE_BLOCK))[:, :11]
    np.testing.assert_array_equal(got, want)
    # the full predict path (while-loop descent on CPU) agrees too
    np.testing.assert_array_equal(b.predict_leaf_index(xt), want)


def test_ordered_mode_end_to_end_matches_default():
    """hist_ordered (ranged sweeps + periodic row re-sort) must produce
    the same trees as the default full-sweep path; predictions agree to
    f32 association noise."""
    import lightgbm_tpu as lgb
    n = 8192 * 2
    rng = np.random.RandomState(0)
    x = rng.randn(n, 6).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
         + 0.3 * rng.randn(n) > 0).astype(np.float32)
    common = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 20, "learning_rate": 0.1, "metric": "",
              "hist_impl": "pallas", "hist_dtype": "float32"}
    b_off = lgb.train({**common, "hist_ordered": "off"},
                      lgb.Dataset(x, label=y), num_boost_round=5,
                      verbose_eval=False)
    b_on = lgb.train({**common, "hist_ordered": "auto",
                      "hist_reorder_every": 2},
                     lgb.Dataset(x, label=y), num_boost_round=5,
                     verbose_eval=False)
    assert all(
        np.array_equal(t1.split_feature_real, t2.split_feature_real)
        and np.array_equal(t1.threshold_bin, t2.threshold_bin)
        for t1, t2 in zip(b_off._gbdt.models, b_on._gbdt.models))
    xt = rng.randn(300, 6).astype(np.float32)
    np.testing.assert_allclose(np.asarray(b_off.predict(xt)),
                               np.asarray(b_on.predict(xt)), atol=2e-5)


def test_ordered_mode_custom_gradients_restore():
    """Switching to custom (file-order) gradients after the ordered mode
    re-sorted rows must restore file order first — trees must match a
    run that never reordered."""
    import lightgbm_tpu as lgb
    n = 8192 * 2
    rng = np.random.RandomState(1)
    x = rng.randn(n, 5).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float32)

    def fobj(scores, ds):
        lab = 2.0 * np.asarray(ds.get_label()) - 1.0
        r = -2.0 * lab / (1.0 + np.exp(2.0 * lab * np.asarray(scores)))
        return r, np.abs(r) * (2.0 - np.abs(r))

    common = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "min_data_in_leaf": 20, "metric": "",
              "hist_impl": "pallas", "hist_dtype": "float32"}

    models = []
    for ordered in ("off", "auto"):
        ds = lgb.Dataset(x, label=y)
        bst = lgb.Booster({**common, "hist_ordered": ordered,
                           "hist_reorder_every": 1}, ds)
        for it in range(4):
            if it < 2:
                bst.update()           # fused path (may re-sort)
            else:
                bst.update(fobj=lambda preds, data: fobj(preds, ds))
        models.append(bst._gbdt.models)
    for t_off, t_on in zip(*models):
        np.testing.assert_array_equal(t_off.split_feature_real,
                                      t_on.split_feature_real)
        np.testing.assert_array_equal(t_off.threshold_bin,
                                      t_on.threshold_bin)


def test_ordered_mode_bagged_matches_default():
    """Ordered-partition mode with BAGGING + feature_fraction (round-3
    extension: file-order mt19937 masks permuted on device) must grow
    the same trees as the full-sweep path."""
    import lightgbm_tpu as lgb
    n = 8192 * 2
    rng = np.random.RandomState(4)
    x = rng.randn(n, 6).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
         + 0.3 * rng.randn(n) > 0).astype(np.float32)
    common = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 20, "learning_rate": 0.1, "metric": "",
              "hist_impl": "pallas", "hist_dtype": "float32",
              # coprime freq/reorder cadence: re-bags must also land on
              # STEADY (non-reorder) iterations so the rebuilt permuted
              # mask feeds both executables
              "bagging_fraction": 0.8, "bagging_freq": 3,
              "feature_fraction": 0.8}
    b_off = lgb.train({**common, "hist_ordered": "off"},
                      lgb.Dataset(x, label=y), num_boost_round=6,
                      verbose_eval=False)
    b_on = lgb.train({**common, "hist_ordered": "auto",
                      "hist_reorder_every": 2},
                     lgb.Dataset(x, label=y), num_boost_round=6,
                     verbose_eval=False)
    for t1, t2 in zip(b_off._gbdt.models, b_on._gbdt.models):
        np.testing.assert_array_equal(t1.split_feature_real,
                                      t2.split_feature_real)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_array_equal(t1.leaf_count, t2.leaf_count)


def test_ordered_mode_lambdarank_matches_default():
    """Round 5: lambdarank is row_permutable — its row_slot map rides
    the ordered-partition permutation and doc_idx remaps through the
    inverse (objectives.LambdarankNDCG.make_row_state_fn), so ranking
    gets the leaf-clustered block sweeps every other family has.  Trees
    must match the never-reordered run exactly."""
    import lightgbm_tpu as lgb
    n = 8192 * 2
    rng = np.random.RandomState(7)
    x = rng.randn(n, 6).astype(np.float32)
    rel = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.5 * rng.randn(n)
    y = np.clip(np.round(rel + 1.5), 0, 4).astype(np.float32)
    group = np.full(n // 16, 16, dtype=np.int32)
    common = {"objective": "lambdarank", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 20, "learning_rate": 0.1, "metric": "",
              "hist_impl": "pallas", "hist_dtype": "float32"}

    def train(ordered):
        ds = lgb.Dataset(x, label=y, group=group)
        return lgb.train({**common, "hist_ordered": ordered,
                          "hist_reorder_every": 2}, ds,
                         num_boost_round=5, verbose_eval=False)

    b_off = train("off")
    b_on = train("auto")
    assert b_on._gbdt._row_order is not None, \
        "permutable lambdarank must have re-sorted rows"
    for t1, t2 in zip(b_off._gbdt.models, b_on._gbdt.models):
        np.testing.assert_array_equal(t1.split_feature_real,
                                      t2.split_feature_real)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_array_equal(t1.leaf_count, t2.leaf_count)
    xt = rng.randn(300, 6).astype(np.float32)
    np.testing.assert_allclose(np.asarray(b_off.predict(xt)),
                               np.asarray(b_on.predict(xt)), atol=2e-5)


def test_dart_banked_matches_host_path_long_drops():
    """The banked DART path must track the host-tree path through long
    drop histories at f32: tree STRUCTURE stays identical, and model
    leaf values replay the recorded drop-factor chain in f64
    (DART._materialize_bank) — bit-identical to the host path's
    numpy-f64 tree.shrinkage sequence wherever the as-trained values
    agree (early trees match exactly; later trees carry the usual f32
    score-rounding divergence between the two paths, bounded here)."""
    import lightgbm_tpu as lgb
    n = 2000
    rng = np.random.RandomState(11)
    x = rng.randn(n, 5).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float32)
    common = {"objective": "binary", "boosting_type": "dart",
              "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 20,
              "drop_rate": 0.3, "metric": ""}
    b_bank = lgb.train(common, lgb.Dataset(x, label=y),
                       num_boost_round=30, verbose_eval=False)
    gb = b_bank._gbdt
    assert gb._bank is not None            # the banked path actually ran

    # host path: same binned dataset, bank disabled up front
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models.gbdt import DART
    from lightgbm_tpu.objectives import create_objective
    cfg = Config.from_params({str(k): str(v) for k, v in common.items()})
    cfg.num_iterations = 30
    ds_inner = lgb.Dataset(x, label=y).inner
    obj = create_objective(cfg)
    obj.init(ds_inner.metadata, ds_inner.num_data)
    host = DART(cfg, ds_inner, obj)
    host._bank_disabled = True             # force the host-tree path
    host._flush_every = 1
    for _ in range(30):
        host.train_one_iter(None, None, False)
    assert host._bank is None

    mb, mh = gb.models, host.models
    assert len(mb) == len(mh) == 30
    exact = 0
    for tb, th in zip(mb, mh):
        np.testing.assert_array_equal(tb.split_feature_real,
                                      th.split_feature_real)
        np.testing.assert_array_equal(tb.threshold_bin, th.threshold_bin)
        np.testing.assert_allclose(tb.leaf_value, th.leaf_value,
                                   rtol=1e-4, atol=1e-6)
        exact += int(np.array_equal(tb.leaf_value, th.leaf_value))
    # the f64 replay is bit-exact while the two paths' f32 scores still
    # agree — several heavily-dropped early trees must match to the bit
    # (the device-dtype compounding this guards against drifted ~1e-4
    # relative on EVERY dropped tree)
    assert exact >= 5, exact
