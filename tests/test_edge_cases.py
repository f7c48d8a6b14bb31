"""Robustness sweep: degenerate shapes and extreme configs must train
without crashing (the reference has no tests at all here; these pin the
padding, trivial-feature, dummy-slot and regularization edge paths)."""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _train(params, x, y, rounds=3, weight=None):
    ds = lgb.Dataset(x, label=y)
    if weight is not None:
        ds.set_weight(weight)
    p = {"min_data_in_leaf": 1, "metric": ""}
    p.update(params)
    return lgb.train(p, ds, num_boost_round=rounds, verbose_eval=False)


@pytest.fixture
def rng():
    """Fresh stream per test so data does not depend on execution order."""
    return np.random.RandomState(0)


def test_single_feature(rng):
    bst = _train({"objective": "regression", "num_leaves": 4},
                 rng.randn(50, 1), rng.randn(50))
    assert bst.predict(rng.randn(10, 1)).shape == (10,)


def test_num_leaves_2_stumps(rng):
    bst = _train({"objective": "binary", "num_leaves": 2},
                 rng.randn(60, 3), (rng.rand(60) > 0.5).astype(float))
    for t in bst._gbdt.models:
        assert t.num_leaves == 2


def test_tiny_dataset(rng):
    _train({"objective": "regression", "num_leaves": 4},
           rng.randn(8, 2), rng.randn(8), rounds=2)


def test_constant_feature_dropped(rng):
    x = rng.randn(100, 3)
    x[:, 1] = 7.0
    bst = _train({"objective": "regression", "num_leaves": 4},
                 x, rng.randn(100), rounds=2)
    assert bst._gbdt.train_data.num_features == 2


def test_max_bin_2(rng):
    _train({"objective": "binary", "num_leaves": 4, "max_bin": 2},
           rng.randn(100, 4), (rng.rand(100) > 0.5).astype(float))


def test_heavy_regularization(rng):
    bst = _train({"objective": "regression", "num_leaves": 8,
                  "lambda_l1": 5.0, "lambda_l2": 10.0},
                 rng.randn(200, 4), rng.randn(200))
    # L1 at this strength clamps most leaf outputs toward zero
    for t in bst._gbdt.models:
        assert np.all(np.abs(t.leaf_value) < 1.0)


def test_max_depth_limits_leaves(rng):
    bst = _train({"objective": "binary", "num_leaves": 32, "max_depth": 2},
                 rng.randn(300, 5), (rng.rand(300) > 0.5).astype(float))
    for t in bst._gbdt.models:
        assert t.num_leaves <= 4          # depth 2 => at most 4 leaves
        assert np.all(t.leaf_depth[:t.num_leaves] <= 2)


def test_mostly_zero_weights(rng):
    w = np.zeros(200)
    w[:10] = 1.0
    _train({"objective": "regression", "num_leaves": 4},
           rng.randn(200, 3), rng.randn(200), rounds=2, weight=w)


def test_data_parallel_tiny_shards(rng):
    _train({"objective": "binary", "tree_learner": "data", "num_shards": 8,
            "num_leaves": 4},
           rng.randn(64, 3), (rng.rand(64) > 0.5).astype(float), rounds=2)


def test_multiclass_two_classes(rng):
    bst = _train({"objective": "multiclass", "num_class": 2,
                  "metric": "multi_logloss", "num_leaves": 4},
                 rng.randn(150, 3), rng.randint(0, 2, 150).astype(float))
    p = bst.predict(rng.randn(20, 3))
    assert p.shape == (2, 20) or p.shape == (20, 2)
    np.testing.assert_allclose(np.asarray(p).reshape(2, -1).sum(axis=0)
                               if p.shape[0] == 2 else p.sum(axis=1),
                               1.0, rtol=1e-5)


def test_lambdarank_query_undercount_fatals(rng):
    """An undercounting .query sidecar must fatal like the reference's
    Metadata::CheckOrPartition, not silently give uncovered rows the
    gradients of query 0 / doc 0 via the row_slot default."""
    from lightgbm_tpu.utils.log import LightGBMError
    x = rng.randn(50, 3)
    y = (rng.rand(50) * 3).astype(np.float64)
    ds = lgb.Dataset(x, label=y)
    ds.set_group([25, 25])
    # bypass set_group's own validation to simulate a bad sidecar load
    ds.inner.metadata.query_boundaries = np.array([0, 20, 40],
                                                  dtype=np.int64)
    with pytest.raises(LightGBMError, match="Sum of query counts"):
        lgb.train({"objective": "lambdarank", "num_leaves": 4,
                   "min_data_in_leaf": 1, "metric": ""},
                  ds, num_boost_round=1, verbose_eval=False)


def test_compile_cache_documented_optout(monkeypatch):
    """utils/compile_cache.py documents LGBM_TPU_NO_COMPILE_CACHE as the
    opt-out; it must actually disable the cache (round-2 doc/flag
    mismatch)."""
    import jax
    from lightgbm_tpu.utils import compile_cache as cc
    monkeypatch.setenv("LGBM_TPU_NO_COMPILE_CACHE", "1")
    monkeypatch.setattr(cc, "_enabled", False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        cc.enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir is None
        assert cc._enabled is False
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture
def cache_config(monkeypatch):
    """enable_compilation_cache() from a clean slate, jax.config
    restored afterwards."""
    import jax
    from lightgbm_tpu.utils import compile_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv("LGBM_TPU_NO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "_enabled", False)
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        yield cc
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)


def test_compile_cache_default_dir_is_fixed_inside_checkout(
        cache_config, monkeypatch, tmp_path):
    """Unset JAX_COMPILATION_CACHE_DIR -> <checkout>/.jax_cache: a fixed
    path (the chip tool keeps what is inside the checkout; a path with
    a pid, time or tempdir component never hits twice)."""
    import jax
    cc = cache_config
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    # the mechanism, without writing into the checkout from a test
    target = str(tmp_path / ".jax_cache")
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", target)
    cc.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == target
    assert os.path.isdir(target)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_compile_cache_env_dir_wins_and_thresholds_still_drop(
        cache_config, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> no directory is set in code (JAX
    reads the variable itself), but sub-second jits are cached there
    too."""
    import jax
    cc = cache_config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ext"))
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR",
                        str(tmp_path / "must_not_exist"))
    cc.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir is None   # untouched
    assert not os.path.exists(str(tmp_path / "must_not_exist"))
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_compile_cache_dir_preset_through_jax_config_is_kept(
        cache_config, monkeypatch, tmp_path):
    """An embedding process that pointed jax.config at its own cache
    keeps it (an installed package's default resolves inside
    site-packages); the thresholds still drop."""
    import jax
    cc = cache_config
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "own"))
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR",
                        str(tmp_path / "must_not_exist"))
    cc.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "own")
    assert not os.path.exists(str(tmp_path / "must_not_exist"))
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_predict_empty_input_preserves_output(rng, tmp_path):
    """Streaming predict must not truncate a previous result file before
    discovering the input is empty (round-2 ADVICE)."""
    from lightgbm_tpu.cli import main
    x = rng.randn(80, 4)
    y = (rng.rand(80) > 0.5).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 4}, x, y)
    model_p = tmp_path / "model.txt"
    bst.save_model(str(model_p))
    empty_p = tmp_path / "empty.tsv"
    empty_p.write_text("")
    out_p = tmp_path / "out.txt"
    out_p.write_text("precious previous result\n")
    rc = main(["task=predict", "data=%s" % empty_p,
               "input_model=%s" % model_p, "output_result=%s" % out_p])
    assert rc != 0
    assert out_p.read_text() == "precious previous result\n"


def test_readme_names_only_config_keys_that_exist():
    """Every backticked `name=value` of README.md whose name carries a
    prefix of the TPU-native keys names a field of Config.  No upstream
    document vouches for these keys, so the README is their only manual,
    and a key that was deleted must leave it too."""
    import dataclasses
    import re

    from lightgbm_tpu.config import Config

    fields = {f.name for f in dataclasses.fields(Config)}
    prefixes = ("hist_", "bag_", "iter_", "serve_", "ingest_", "snapshot_",
                "refresh_")
    readme = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                          "README.md")
    with open(readme) as f:
        named = {m.group(1) for m in re.finditer(
            r"`([a-z][a-z0-9_]*)=[^`]*`", f.read())
            if m.group(1).startswith(prefixes)}
    assert len(named) >= 10, named      # the pattern still finds them
    assert named <= fields, sorted(named - fields)
