"""Fused Pallas histogram+gain kernel (config.hist_fused) and the
hist_acc accumulator modes + IO/compute-overlapped shard streaming
(config.ingest_prefetch).

Parity convention: hist_fused=off IS the retained two-op oracle (the
bag_compact pattern) — and because the fused kernel runs the oracle's
exact jnp scan ops on the exact accumulator values, fused-on is
BIT-parity with it in interpret mode: kernel outputs, grow_tree trees
and whole saved models compare exactly, across {masked, ranged,
blocklist} x {binary, multiclass, lambdarank}.  bf16/i32 accumulators
round their inputs, so they are opt-in with tolerance spot-checks
(counts exact for i32).  The prefetcher changes WHEN windows stage,
never their order or bytes, so shard-fed models stay byte-identical
with overlap on or off.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops.hist_pallas import (PALLAS_ROW_BLOCK,
                                          fold_leaf_mask,
                                          leaf_histogram_blocklist_fused,
                                          leaf_histogram_masked,
                                          leaf_histogram_masked_fused,
                                          leaf_histogram_ranged_fused,
                                          make_gh2, make_gh2_acc)
from lightgbm_tpu.ops.split import (SplitParams, find_best_split,
                                    find_best_split_fused)
from lightgbm_tpu.utils.log import LightGBMError

# every test in this module must leave no worker threads
pytestmark = pytest.mark.usefixtures("no_leaked_threads")


# ---------------------------------------------------------------------------
# kernel-level parity vs the two-op oracle
# ---------------------------------------------------------------------------

def _kernel_case(n=512, f=9, b=63, seed=0, row_block=128):
    """bins/gh/leaf_eff plus a parent covering leaves {2, 3}; target
    leaf 2 is the 'small child', 3 the subtracted sibling."""
    rng = np.random.RandomState(seed)
    bins = jnp.asarray(rng.randint(0, b, size=(f, n)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray((rng.rand(n) + 0.1).astype(np.float32))
    leaf_id = jnp.asarray(rng.randint(0, 4, size=n).astype(np.int32))
    bag = jnp.asarray(rng.rand(n) < 0.8)
    leaf_eff = fold_leaf_mask(leaf_id, bag)
    gh2 = make_gh2(grad, hess)
    parent_eff = fold_leaf_mask(
        jnp.zeros(n, jnp.int32),
        ((leaf_id == 2) | (leaf_id == 3)) & bag)
    parent = leaf_histogram_masked(bins, gh2, parent_eff, jnp.int32(0),
                                   max_bin=b, row_block=row_block,
                                   interpret=True)
    small = leaf_histogram_masked(bins, gh2, leaf_eff, jnp.int32(2),
                                  max_bin=b, row_block=row_block,
                                  interpret=True)
    large = parent - small

    def stats(h):
        return (jnp.round(jnp.sum(h[0, :, 2])).astype(jnp.int32),
                jnp.sum(h[0, :, 0]), jnp.sum(h[0, :, 1]))

    return dict(bins=bins, grad=grad, hess=hess, gh2=gh2,
                leaf_eff=leaf_eff, parent=parent, small=small,
                large=large, s_stats=stats(small), l_stats=stats(large),
                fmask=jnp.ones(f, bool),
                params=SplitParams(5, 1e-3, 0.1, 0.2, 0.0), b=b, n=n,
                row_block=row_block)


def _assert_best_equal(want, got, msg=""):
    for fld in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, fld)), np.asarray(getattr(got, fld)),
            err_msg="%s field %s" % (msg, fld))


# f = 9 is one feature block larger than the array, 39 has a ragged
# third block: the kernels read the bin matrix in place either way
@pytest.mark.parametrize("f", [9, 39])
def test_fused_masked_kernel_bit_identical(f):
    """Fused sweep: histogram bit-equal to the plain kernel, and the
    per-feature rows finish to the EXACT BestSplit the two-op oracle
    (find_best_split over the materialized tensor) produces — for the
    swept child and the subtracted sibling."""
    c = _kernel_case(f=f)
    hist, pfs, pfl = leaf_histogram_masked_fused(
        c["bins"], c["gh2"], c["leaf_eff"], jnp.int32(2), c["parent"],
        c["fmask"], c["s_stats"], c["l_stats"], None, max_bin=c["b"],
        params=c["params"], row_block=c["row_block"], interpret=True)
    assert jnp.array_equal(hist, c["small"])
    cs, sgs, shs = c["s_stats"]
    cl, sgl, shl = c["l_stats"]
    _assert_best_equal(
        find_best_split(c["small"], cs, sgs, shs, c["fmask"], c["params"]),
        find_best_split_fused(pfs, sgs, shs, c["params"]), "small")
    _assert_best_equal(
        find_best_split(c["large"], cl, sgl, shl, c["fmask"], c["params"]),
        find_best_split_fused(pfl, sgl, shl, c["params"]), "large")


@pytest.mark.parametrize("f", [9, 39])
def test_fused_blocklist_and_ranged_bit_identical(f):
    """The ordered-partition fused variants: full block list == full
    sweep == masked fused, per-feature rows included; a partial list
    covering the target's blocks is bit-identical too."""
    c = _kernel_case(n=1024, f=f, row_block=128)
    nblk = c["n"] // c["row_block"]
    want = leaf_histogram_masked_fused(
        c["bins"], c["gh2"], c["leaf_eff"], jnp.int32(2), c["parent"],
        c["fmask"], c["s_stats"], c["l_stats"], None, max_bin=c["b"],
        params=c["params"], row_block=c["row_block"], interpret=True)
    got_b = leaf_histogram_blocklist_fused(
        c["bins"], c["gh2"], c["leaf_eff"], jnp.int32(2),
        jnp.arange(nblk, dtype=jnp.int32), jnp.int32(nblk), c["parent"],
        c["fmask"], c["s_stats"], c["l_stats"], None, max_bin=c["b"],
        params=c["params"], row_block=c["row_block"], interpret=True)
    got_r = leaf_histogram_ranged_fused(
        c["bins"], c["gh2"], c["leaf_eff"], jnp.int32(2), jnp.int32(0),
        jnp.int32(nblk), c["parent"], c["fmask"], c["s_stats"],
        c["l_stats"], None, max_bin=c["b"], params=c["params"],
        row_block=c["row_block"], interpret=True)
    for got in (got_b, got_r):
        for w, g in zip(want, got):
            assert jnp.array_equal(w, g)
    # partial list: clamp the sweep to the blocks that actually hold
    # target rows (here: rows are uniform, so list every block that has
    # a leaf-2 row — prove the n_active < grid path keeps parity)
    occ = np.asarray(c["leaf_eff"]).reshape(nblk, c["row_block"])
    hit = np.flatnonzero((occ == 2).any(axis=1)).astype(np.int32)
    blist = np.zeros(nblk, np.int32)
    blist[:len(hit)] = hit
    got_p = leaf_histogram_blocklist_fused(
        c["bins"], c["gh2"], c["leaf_eff"], jnp.int32(2),
        jnp.asarray(blist), jnp.int32(len(hit)), c["parent"],
        c["fmask"], c["s_stats"], c["l_stats"], None, max_bin=c["b"],
        params=c["params"], grid_blocks=nblk,
        row_block=c["row_block"], interpret=True)
    for w, g in zip(want, got_p):
        assert jnp.array_equal(w, g)


def test_hist_acc_modes_spot_check():
    """bf16/int32 accumulators at the hist_ordered ulp bar style:
    values close to the f32 kernel at mode-appropriate tolerances
    (bf16 rounds inputs to 8-bit mantissas; i32 quantizes at
    2^30/N granularity), and the i32 COUNT component is exact — the
    reason integer accumulation exists."""
    c = _kernel_case()
    for acc, rtol, atol in (("bf16", 2e-2, 2e-2), ("i32", 1e-4, 1e-4)):
        gh2a, inv = make_gh2_acc(c["grad"], c["hess"], acc)
        got = leaf_histogram_masked(
            c["bins"], gh2a, c["leaf_eff"], jnp.int32(2), max_bin=c["b"],
            hist_acc=acc, inv_scale=inv, row_block=c["row_block"],
            interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(c["small"]),
                                   rtol=rtol, atol=atol, err_msg=acc)
        if acc == "i32":
            np.testing.assert_array_equal(
                np.asarray(got[:, :, 2]), np.asarray(c["small"][:, :, 2]),
                err_msg="i32 counts must be exact")
        # the fused variant runs the same accumulators end to end
        hist, pfs, pfl = leaf_histogram_masked_fused(
            c["bins"], gh2a, c["leaf_eff"], jnp.int32(2), c["parent"],
            c["fmask"], c["s_stats"], c["l_stats"], inv,
            max_bin=c["b"], params=c["params"], hist_acc=acc,
            row_block=c["row_block"], interpret=True)
        assert jnp.array_equal(hist, got)
        assert np.isfinite(np.asarray(pfs)[:, 2:]).all()


# ---------------------------------------------------------------------------
# grow_tree: fused vs the two-op oracle, bit-identical trees
# ---------------------------------------------------------------------------

def _grow_case(n, f=6, b=64, seed=0):
    rng = np.random.RandomState(seed)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    grad = (bins_t[0] / b - 0.5 + 0.2 * rng.randn(n)).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    bag = rng.rand(n) < 0.9
    return (jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(bag), jnp.ones(f, dtype=bool))


@pytest.mark.parametrize("variant", ["plain", "ranged", "pooled"])
def test_grow_tree_fused_bit_identical(variant):
    from lightgbm_tpu.ops.grow import grow_tree

    n = PALLAS_ROW_BLOCK * (2 if variant == "ranged" else 1)
    args = _grow_case(n)
    kw = dict(max_leaves=8, max_bin=64,
              params=SplitParams(20, 1.0, 0.0, 0.0, 0.0),
              hist_impl="pallas")
    if variant == "ranged":
        kw["ranged"] = True
    if variant == "pooled":
        kw["hist_slots"] = 3
    t0, l0 = grow_tree(*args, fused=False, **kw)
    t1, l1 = grow_tree(*args, fused=True, **kw)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    for fld in t0._fields:
        np.testing.assert_array_equal(np.asarray(getattr(t0, fld)),
                                      np.asarray(getattr(t1, fld)),
                                      err_msg=fld)


# ---------------------------------------------------------------------------
# e2e: the objective x learner matrix, whole models byte-identical
# ---------------------------------------------------------------------------

def _data_for(objective, n, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 6).astype(np.float32)
    signal = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(n)
    if objective == "binary":
        return x, (signal > 0).astype(np.float32), None
    if objective == "multiclass":
        edges = np.quantile(signal, [1 / 3, 2 / 3])
        return x, np.digitize(signal, edges).astype(np.float32), None
    assert objective == "lambdarank"
    y = np.clip(np.round(signal + 1.5), 0, 4).astype(np.float32)
    return x, y, np.full(n // 16, 16, dtype=np.int32)


def _params_for(objective):
    # 7 leaves / 2 rounds keep the interpret-mode matrix inside the
    # tier-1 time budget; every fused kernel variant still runs
    # (ordered=auto drives the blocklist ladder, off the masked kernel)
    p = {"objective": objective, "num_leaves": 7, "max_bin": 63,
         "min_data_in_leaf": 20, "learning_rate": 0.1, "metric": "",
         "hist_impl": "pallas", "hist_dtype": "float32",
         "bagging_fraction": 0.6, "bagging_freq": 2}
    if objective == "multiclass":
        p.update(num_class=3, metric="multi_logloss")
    return p


def _train(params, x, y, group=None, rounds=2):
    ds = lgb.Dataset(x, label=y, group=group)
    return lgb.train(params, ds, num_boost_round=rounds,
                     verbose_eval=False)


@pytest.mark.parametrize("objective",
                         ["binary", "multiclass", "lambdarank"])
@pytest.mark.parametrize("ordered", ["auto", "off"])
def test_fused_models_byte_identical_to_oracle(objective, ordered):
    """hist_fused=on (fused kernels: masked under ordered=off, the
    blocklist ladder under ordered=auto) trains the BYTE-identical
    model to hist_fused=off across the objective matrix — stronger
    than the bag_compact structure+ulp bar, because the fused scan is
    the oracle's own op sequence."""
    n = PALLAS_ROW_BLOCK
    x, y, group = _data_for(objective, n, seed=7)
    common = {**_params_for(objective), "hist_ordered": ordered,
              "hist_reorder_every": 2}
    b_off = _train({**common, "hist_fused": "off"}, x, y, group)
    b_on = _train({**common, "hist_fused": "on"}, x, y, group)
    assert b_off._gbdt.hist_fused is False
    assert b_on._gbdt.hist_fused is True
    ms_off, ms_on = b_off._gbdt.models, b_on._gbdt.models
    assert len(ms_off) == len(ms_on) > 0
    for i, (t0, t1) in enumerate(zip(ms_off, ms_on)):
        assert t0.to_string() == t1.to_string(), "tree %d differs" % i


def test_fused_zero_recompiles_steady_state(xla_guard):
    """Fused steady state keeps the zero-recompile invariant: after
    warm-up (incl. one re-bagging boundary), further fused iterations
    across another re-bag trigger ZERO XLA compiles."""
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    n = PALLAS_ROW_BLOCK
    x, y, _ = _data_for("binary", n, seed=3)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "min_data_in_leaf": 20, "metric": "",
              "hist_impl": "pallas", "hist_fused": "on",
              "hist_ordered": "off", "bagging_fraction": 0.5,
              "bagging_freq": 2, "bag_compact": "off",
              "num_iterations": 16}
    ds = lgb.Dataset(x, label=y, params=params)
    cfg = Config.from_params({k: str(v) for k, v in params.items()})
    inner = ds.inner
    obj = create_objective(cfg)
    obj.init(inner.metadata, inner.num_data)
    booster = create_boosting(cfg, inner, obj)
    for _ in range(3):   # warm-up crosses the first re-bag (freq=2)
        booster.train_one_iter(None, None, False)
    jax.block_until_ready(booster.scores)
    with xla_guard(0, what="fused histogram+gain steady state across "
                          "a further re-bagging boundary"):
        for _ in range(2):   # iterations 3..4: re-bag at 4
            booster.train_one_iter(None, None, False)
        jax.block_until_ready(booster.scores)


# ---------------------------------------------------------------------------
# config validation + gate composition (satellite)
# ---------------------------------------------------------------------------

def test_config_rejects_unknown_knob_values():
    with pytest.raises(LightGBMError, match="hist_fused"):
        Config.from_params({"hist_fused": "maybe"})
    with pytest.raises(LightGBMError, match="hist_acc"):
        Config.from_params({"hist_acc": "f16"})
    with pytest.raises(LightGBMError, match="ingest_prefetch"):
        Config.from_params({"ingest_prefetch": "-1"})
    # explicit xla forfeits the Pallas-only modes loudly, not silently
    with pytest.raises(LightGBMError, match="hist_acc"):
        Config.from_params({"hist_impl": "xla", "hist_acc": "bf16"})
    with pytest.raises(LightGBMError, match="hist_fused"):
        Config.from_params({"hist_impl": "xla", "hist_fused": "on"})


def test_hist_acc_requires_pallas_at_train_time():
    """hist_impl=auto resolves to xla on CPU — a non-f32 accumulator
    must fatal at booster construction, mirroring the hist_impl=pallas
    prerequisite checks."""
    x, y, _ = _data_for("binary", 1200, seed=1)
    with pytest.raises(LightGBMError, match="hist_acc"):
        _train({"objective": "binary", "num_leaves": 7, "max_bin": 63,
                "min_data_in_leaf": 20, "metric": "",
                "hist_acc": "bf16"}, x, y)


def test_hist_acc_composes_with_bag_compact_auto_gate():
    """The bag_compact auto-gate keys on hist_dtype=float32 (the f64
    PARITY configuration keeps the masked oracle).  hist_acc=bf16/i32
    still runs f32 hist_dtype, so compaction must stay ENGAGED — the
    accumulator mode and the window compaction are independent axes."""
    n = PALLAS_ROW_BLOCK * 2   # window (8192) must stay under n_pad
    x, y, _ = _data_for("binary", n, seed=5)
    base = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
            "min_data_in_leaf": 20, "metric": "",
            "hist_impl": "pallas", "hist_ordered": "off",
            "bagging_fraction": 0.4, "bagging_freq": 2}
    for acc in ("bf16", "i32"):
        b = _train({**base, "hist_acc": acc}, x, y, rounds=2)
        g = b._gbdt
        assert g.hist_acc == acc
        assert g._bag_window and g._bag_arranged, \
            "bag_compact auto must stay engaged under hist_acc=%s" % acc


def test_hist_acc_models_close_to_f32():
    """Opt-in accumulator spot check at the hist_ordered e2e bar:
    structure may differ in knife-edge gain ties, so the bar is
    prediction closeness, with i32 much tighter than bf16."""
    n = PALLAS_ROW_BLOCK
    x, y, _ = _data_for("binary", n, seed=9)
    base = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
            "min_data_in_leaf": 20, "metric": "",
            "hist_impl": "pallas", "hist_ordered": "off",
            "bag_compact": "off"}
    b_f32 = _train(base, x, y, rounds=2)
    xt = np.random.RandomState(5).randn(256, 6).astype(np.float32)
    want = np.asarray(b_f32.predict(xt))
    for acc, atol in (("i32", 5e-3), ("bf16", 5e-2)):
        b = _train({**base, "hist_acc": acc}, x, y, rounds=2)
        np.testing.assert_allclose(np.asarray(b.predict(xt)), want,
                                   atol=atol, err_msg=acc)


# ---------------------------------------------------------------------------
# IO/compute-overlapped shard streaming (config.ingest_prefetch)
# ---------------------------------------------------------------------------

def test_prefetch_windows_preserves_order_and_bytes():
    from lightgbm_tpu.ingest.shards import prefetch_windows

    rng = np.random.RandomState(0)
    src = [rng.randint(0, 255, size=(4, k)).astype(np.uint8)
           for k in (96, 96, 17)]
    want = [w.copy() for w in src]
    for depth in (0, 1, 3, 16):
        got = list(prefetch_windows(iter(src), depth))
        assert len(got) == len(want)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
            assert g.flags["C_CONTIGUOUS"]


def test_prefetch_windows_propagates_exceptions_and_aborts_clean():
    import threading

    from lightgbm_tpu.ingest.shards import prefetch_windows

    def bad():
        yield np.zeros((2, 8), np.uint8)
        raise IOError("shard vanished")

    it = prefetch_windows(bad(), 2)
    next(it)
    with pytest.raises(IOError, match="shard vanished"):
        next(it)

    # early consumer abandonment must not leave a producer thread
    # blocked on the bounded queue
    before = threading.active_count()

    def many():
        for _ in range(64):
            yield np.zeros((2, 8), np.uint8)

    it2 = prefetch_windows(many(), 1)
    next(it2)
    it2.close()
    deadline = 50
    while threading.active_count() > before and deadline:
        import time
        time.sleep(0.05)
        deadline -= 1
    assert threading.active_count() <= before, \
        "prefetch producer thread leaked after consumer close"


def test_shard_fed_training_byte_identical_with_prefetch(tmp_path):
    """The acceptance gate: shard-fed models are byte-identical to the
    in-memory text path with overlap ON (ingest_prefetch=3), and to the
    synchronous shard feed (ingest_prefetch=0) — the prefetcher may
    change timing, never bytes."""
    from test_ingest import _train_model, _write_tsv
    from lightgbm_tpu.ingest.writer import ingest

    p = _write_tsv(tmp_path)
    out = str(tmp_path / "shards")
    ingest([p], out, Config.from_params(
        {"ingest_workers": "1", "ingest_shard_rows": "96"}))
    text = _train_model(p, tmp_path, "text")
    sync = _train_model(out, tmp_path, "sync",
                        extra={"ingest_prefetch": "0"})
    overlapped = _train_model(out, tmp_path, "pref",
                              extra={"ingest_prefetch": "3"})
    assert sync == text
    assert overlapped == text
