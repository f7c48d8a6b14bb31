"""Runtime-guard tests: the compile budgets the performance story rests on.

  * guard mechanics: track_compiles counts first-compiles and counts
    nothing on steady-state dispatches; compile_budget raises.
  * serving: after warm(serve_max_batch_rows=64), mixed-size requests
    across every mode — direct and through the micro-batcher — compile
    NOTHING (the power-of-two pre-compile contract, PR 2).
  * training: two identical in-process trainings compile only in the
    first run — the fused step really is one compile per
    (shape, config) (the compile-amortization contract, PR 1).
  * serving metrics: the lock-discipline regression the GL006 audit
    demanded (threaded hammer on the counters).
"""

import os
import threading
import types

import numpy as np
import pytest

from lightgbm_tpu.analysis.guards import (GuardViolation, compile_budget,
                                          track_compiles)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


# ---------------------------------------------------------------------------
# guard mechanics
# ---------------------------------------------------------------------------

def test_track_compiles_counts_first_and_not_steady_state():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    with track_compiles() as first:
        f(jnp.ones(17))
    assert first.compiles >= 1

    with track_compiles() as steady:
        for _ in range(3):
            f(jnp.ones(17))
    assert steady.compiles == 0, steady.summary()

    with track_compiles() as reshaped:
        f(jnp.ones(18))          # new shape: must recompile
    assert reshaped.compiles >= 1


def test_compile_budget_raises_with_executable_names():
    import jax
    import jax.numpy as jnp

    g = jax.jit(lambda x: x - 2)
    with pytest.raises(GuardViolation) as ex:
        with compile_budget(0, what="budget probe"):
            g(jnp.ones(23))
    assert "budget probe" in str(ex.value)
    assert "compile" in str(ex.value)


def test_xla_guard_fixture_is_compile_budget(xla_guard):
    assert xla_guard is compile_budget


# ---------------------------------------------------------------------------
# serving: zero recompiles in steady state (satellite + acceptance)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def warm_forest():
    from lightgbm_tpu.serving.forest import ServingForest

    with open(os.path.join(GOLDEN, "golden_binary_model.txt")) as f:
        forest = ServingForest(f.read(), backend="jax")
    assert forest.engine == "jax"
    forest.warm(64)
    return forest


def _rows(n, width, seed):
    # deterministic feature rows (values near the model's thresholds
    # don't matter here; only shapes drive compilation)
    base = np.linspace(-1.0, 1.0, n * width, dtype=np.float64)
    return np.roll(base, seed).reshape(n, width)


def test_serving_steady_state_zero_recompiles(warm_forest, xla_guard):
    width = warm_forest.max_feature_idx + 1
    sizes = [1, 2, 3, 15, 16, 17, 31, 40, 63, 64, 5, 64, 1]
    with xla_guard(0, what="serving steady state (direct predict)"):
        for i, n in enumerate(sizes):
            for mode in ("raw", "normal", "leaf"):
                res = warm_forest.predict(_rows(n, width, i), mode)
                if mode == "leaf":
                    assert res.shape == (n, warm_forest.num_models)
                else:
                    assert res.shape == (1, n)


def test_serving_steady_state_zero_recompiles_through_batcher(
        warm_forest, xla_guard):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.serving.server import ServingState

    cfg = Config.from_params({"task": "serve", "serve_max_batch_rows": "64",
                              "serve_batch_timeout_ms": "1"})
    state = ServingState(cfg, warm_forest)
    width = warm_forest.max_feature_idx + 1
    from lightgbm_tpu.serving.batcher import RowsPayload
    try:
        with xla_guard(0, what="serving steady state (batched)"):
            results = []
            threads = [
                threading.Thread(target=lambda i=i: results.append(
                    state.batcher.submit(
                        (warm_forest, "raw", ("rows",)),
                        RowsPayload(_rows(7 + i, width, i)))))
                for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert len(results) == 6
    finally:
        state.batcher.shutdown()


def test_warm_forest_compiles_every_bucket_upfront(xla_guard):
    # warm() itself is WHERE the compiles happen; afterwards even a
    # never-seen batch size stays inside the compiled bucket set
    from lightgbm_tpu.serving.forest import ServingForest

    with open(os.path.join(GOLDEN, "golden_binary_model.txt")) as f:
        text = f.read()
    forest = ServingForest(text, backend="jax")
    n_buckets = forest.warm(64)
    assert n_buckets == 3            # 16, 32, 64
    width = forest.max_feature_idx + 1
    with xla_guard(0, what="post-warm first-ever sizes"):
        for n in (9, 23, 57):
            forest.predict(_rows(n, width, n), "raw")


# ---------------------------------------------------------------------------
# training: one compile per (shape, config) (acceptance)
# ---------------------------------------------------------------------------

def _train_once():
    from lightgbm_tpu.api import Dataset, train

    rng_free = np.linspace(0.0, 1.0, 240 * 5)  # deterministic, no RNG
    x = np.sin(rng_free * 17.0).reshape(240, 5)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7,
              "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
              "num_iterations": 4, "verbose": 0}
    ds = Dataset(x, label=y, params=params)
    booster = train(params, ds, num_boost_round=4, verbose_eval=False)
    # force the tree flush (device -> host) like any real consumer
    return booster.model_to_string()


def test_fused_training_step_compiles_once_per_shape_config():
    with track_compiles() as first:
        m1 = _train_once()
    assert first.compiles > 0        # the run that pays

    with track_compiles() as second:
        m2 = _train_once()
    assert m2 == m1                  # bit-identical retrain
    assert second.compiles == 0, (
        "an identical (shape, config) training retraced: "
        + second.summary())


def test_fused_training_step_recompiles_only_for_new_config():
    _train_once()                    # ensure the base config is warm
    with track_compiles() as changed:
        from lightgbm_tpu.api import Dataset, train

        x = np.sin(np.linspace(0.0, 1.0, 240 * 5) * 17.0).reshape(240, 5)
        y = (x.sum(axis=1) > 0).astype(np.float32)
        params = {"objective": "binary", "num_leaves": 15,  # new config
                  "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
                  "num_iterations": 2, "verbose": 0}
        train(params, Dataset(x, label=y, params=params),
              num_boost_round=2, verbose_eval=False)
    assert changed.compiles > 0      # a NEW config must compile


# ---------------------------------------------------------------------------
# iteration batching: one compile per (K, shape, config), zero recompiles
# across segments of the same K and across re-bag boundaries under the
# scan (the _get_fused_step key includes K — satellite)
# ---------------------------------------------------------------------------

def _batched_booster(extra=None, n=400):
    from lightgbm_tpu.api import Dataset
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    rng_free = np.linspace(0.0, 1.0, n * 5)
    x = np.sin(rng_free * 17.0).reshape(n, 5)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7,
              "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
              "verbose": 0, **(extra or {})}
    ds = Dataset(x, label=y, params=params)
    cfg = Config.from_params({k: str(v) for k, v in params.items()})
    obj = create_objective(cfg)
    obj.init(ds.inner.metadata, ds.inner.num_data)
    return create_boosting(cfg, ds.inner, obj)


def _drive(booster, n):
    done = 0
    while done < n:
        _, k = booster.train_segment(n - done, is_eval=False)
        done += k


def test_iter_batched_one_compile_per_k_no_retrace_across_segments():
    """iter_batch=4 over 10 rounds segments as 4, 4, 2: the first K=4
    and K=2 segments compile; the SECOND K=4 segment (and a whole
    fresh same-config booster) must hit the cached executables — a
    mid-run K change lands on a distinct cache entry instead of
    retracing the shared one."""
    import jax

    a = _batched_booster({"iter_batch": 4, "num_iterations": 10})
    _drive(a, 4)                      # compiles the K=4 executable
    jax.block_until_ready(a.scores)
    with compile_budget(0, what="second K=4 segment (same executable)"):
        _drive(a, 4)
        jax.block_until_ready(a.scores)
    with track_compiles() as short_seg:
        _drive(a, 2)                  # the K=2 final segment
        jax.block_until_ready(a.scores)
    assert short_seg.compiles > 0     # distinct entry for K=2
    assert len(a.models) == 10        # flush materializes all 10 trees

    b = _batched_booster({"iter_batch": 4, "num_iterations": 10})
    with compile_budget(0, what="fresh same-config batched training"):
        _drive(b, 10)
        jax.block_until_ready(b.scores)


def test_iter_batched_zero_recompiles_across_rebag_boundaries(
        xla_guard):
    """Re-bagging epochs under the scan: after one full warm cycle,
    further segments crossing re-bag boundaries (mask redraw + packed
    upload + batched fused steps) trigger ZERO compiles."""
    import jax

    g = _batched_booster({"iter_batch": 2, "bagging_fraction": 0.5,
                          "bagging_freq": 2, "num_iterations": 12})
    _drive(g, 4)                      # warm: two K=2 segments + re-bag
    jax.block_until_ready(g.scores)
    with xla_guard(0, what="batched segments across two re-bag "
                           "boundaries"):
        _drive(g, 6)                  # re-bags at 4, 6, 8
        jax.block_until_ready(g.scores)


def test_pallas_step_zero_recompiles_across_rebag_boundary(xla_guard):
    """The Pallas step (masked sweep kernel + XLA gain scan) keeps the
    zero-recompile invariant: after warm-up (incl. one re-bagging
    boundary), further iterations across another re-bag trigger ZERO
    XLA compiles."""
    import jax

    booster = _batched_booster(
        {"max_bin": 63, "min_data_in_leaf": 20, "metric": "",
         "hist_impl": "pallas", "hist_ordered": "off",
         "bagging_fraction": 0.5, "bagging_freq": 2,
         "bag_compact": "off", "num_iterations": 16})
    for _ in range(3):   # warm-up crosses the first re-bag (freq=2)
        booster.train_one_iter(None, None, False)
    jax.block_until_ready(booster.scores)
    with xla_guard(0, what="Pallas-kernel steady state across a "
                           "further re-bagging boundary"):
        for _ in range(2):   # iterations 3..4: re-bag at 4
            booster.train_one_iter(None, None, False)
        jax.block_until_ready(booster.scores)


def test_iter_batched_model_matches_oracle_bytes():
    from lightgbm_tpu.api import Dataset, train

    def text(k):
        rng_free = np.linspace(0.0, 1.0, 240 * 5)
        x = np.sin(rng_free * 17.0).reshape(240, 5)
        y = (x.sum(axis=1) > 0).astype(np.float32)
        params = {"objective": "binary", "num_leaves": 7,
                  "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
                  "num_iterations": 6, "verbose": 0, "iter_batch": k}
        b = train(params, Dataset(x, label=y, params=params),
                  num_boost_round=6, verbose_eval=False)
        return b.model_to_string()

    assert text("4") == text("1")


# ---------------------------------------------------------------------------
# serving metrics lock-discipline regression (GL006 audit)
# ---------------------------------------------------------------------------

def test_serving_metrics_counters_survive_threaded_hammer():
    from lightgbm_tpu.serving.server import Metrics

    m = Metrics()
    n, nthreads = 400, 8

    def worker():
        for _ in range(n):
            m.request_started("/predict")
            m.batch_dispatched(1, 2)
            m.request_finished("/predict", 200, 0.001, rows=2)

    threads = [threading.Thread(target=worker) for _ in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)

    total = n * nthreads
    assert m.in_flight == 0
    assert m.requests[("/predict", 200)] == total
    assert m.rows_total == 2 * total
    assert m.batches_total == total
    assert sum(m.latency.counts) == total
    assert sum(m.batch_rows.counts) == total
    # render under concurrent load must not corrupt either
    fake_forest = types.SimpleNamespace(loaded_at=0.0, num_models=1)
    blob = m.render(fake_forest)
    assert b"lgbm_serve_rows_total %d" % (2 * total) in blob
