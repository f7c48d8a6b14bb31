"""Iteration-batched training (config.iter_batch): K boosting rounds
scanned into one device dispatch must be BIT-PARITY with the
per-iteration oracle (iter_batch=1).

The scan wrapper (models/gbdt.py _batch_iters) iterates the very same
fused step closure the K=1 path jits, and the segment scheduler
(_plan_segment) ends segments at every host-observable boundary
(metric lines, early stopping, re-bagging epochs, re-sort cadence,
checkpoints), so the model TEXT — not just the structure — must be
byte-identical for any K, including an odd K that does not divide the
round count.  K values cover {2, 8, odd non-divisor 3}; the axes cover
{binary, regression, multiclass, lambdarank} x {plain, bagged with a
re-bag boundary INSIDE the requested segment} x DART x
tree_learner=data, plus checkpoint/resume mid-segment and early
stopping at the same iteration.
"""

import os
import re

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.models.gbdt import create_boosting
from lightgbm_tpu.objectives import create_objective


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _data_for(objective, n, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 6).astype(np.float32)
    signal = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(n)
    if objective == "binary":
        return x, (signal > 0).astype(np.float32), None
    if objective == "regression":
        return x, signal.astype(np.float32), None
    if objective == "multiclass":
        edges = np.quantile(signal, [1 / 3, 2 / 3])
        return x, np.digitize(signal, edges).astype(np.float32), None
    assert objective == "lambdarank"
    y = np.clip(np.round(signal + 1.5), 0, 4).astype(np.float32)
    return x, y, np.full(n // 16, 16, dtype=np.int32)


def _params_for(objective):
    p = {"objective": objective, "num_leaves": 7, "max_bin": 63,
         "min_data_in_leaf": 20, "learning_rate": 0.1, "metric": ""}
    if objective == "multiclass":
        p.update(num_class=3, metric="multi_logloss")
    return p


def _model_text(params, x, y, group=None, rounds=10):
    ds = lgb.Dataset(x, label=y, group=group)
    b = lgb.train(params, ds, num_boost_round=rounds, verbose_eval=False)
    return b.model_to_string()


# ---------------------------------------------------------------------------
# the parity matrix: objectives x K, plain and bagged
# ---------------------------------------------------------------------------

# float32 ulps by which a float of a LAMBDARANK model may differ between
# K=1 and K>1.  A document's lambda is `p_lam.sum(axis=2) -
# p_lam.sum(axis=1)` over the query's [L, L] pair terms
# (objectives.py make_grad_fn).  Outside a loop XLA:CPU fuses the two
# reductions and their shared producer into ONE loop nest; in the body of
# the K-scan's `while` it emits each reduction as a fusion of its own,
# and the axis-1 sum then adds in another order.  Each sum moves by a few
# ulps, their DIFFERENCE (the two nearly cancel) by up to 7e-5 of itself
# at L = 16, and leaf values and gains, ratios of sums of those over >= 20
# rows, by up to 2.5e-5 (424 ulps) in these jobs.  Every K > 1 agrees with
# every other to the byte; splits, thresholds and counts agree with K=1's.
# Pinning the order would mean materialising the pair tensors
# (`optimization_barrier`), 0.1 s a tree of HBM traffic at the benchmark's
# ranking cell: not free, so the comparison states its width (PERF.md
# section 6, PR 31; ROADMAP D9).
RANK_MODEL_ULPS = 1024


def _assert_model_matches(got, oracle, objective, what):
    if objective != "lambdarank":
        assert got == oracle, what
        return
    a, b = (re.split(r"[\s=]+", t) for t in (got, oracle))
    assert len(a) == len(b), what
    width = RANK_MODEL_ULPS * float(np.finfo(np.float32).eps)
    for u, v in zip(a, b):
        if u != v:      # every other token, names and counts, is equal
            fu, fv = float(u), float(v)
            assert abs(fu - fv) <= width * max(abs(fu), abs(fv)), (
                what, u, v)


@pytest.mark.parametrize("objective",
                         ["binary", "regression", "multiclass",
                          "lambdarank"])
def test_batched_matches_oracle(objective):
    """Model text byte-identity for K in {2, 8, odd non-divisor 3}
    against the K=1 oracle, 10 rounds (so K=8 leaves a short final
    segment and K=3 never tiles the count)."""
    n = 1600
    x, y, group = _data_for(objective, n, seed=11)
    base = _params_for(objective)
    oracle = _model_text({**base, "iter_batch": "1"}, x, y, group)
    for k in ("2", "8", "3"):
        got = _model_text({**base, "iter_batch": k}, x, y, group)
        _assert_model_matches(got, oracle, objective,
                              "iter_batch=%s diverged (%s)" % (k, objective))


@pytest.mark.parametrize("objective", ["binary", "multiclass"])
def test_batched_bagged_rebag_inside_segment(objective):
    """bagging_freq=3 with iter_batch=8: every requested segment
    straddles a re-bagging boundary, so the scheduler must cut segments
    at the epoch edge — models stay byte-identical and mask draws stay
    on the sequential mt19937 stream."""
    n = 1600
    x, y, group = _data_for(objective, n, seed=5)
    base = {**_params_for(objective), "bagging_fraction": 0.5,
            "bagging_freq": 3}
    oracle = _model_text({**base, "iter_batch": "1"}, x, y, group,
                         rounds=9)
    for k in ("8", "2"):
        got = _model_text({**base, "iter_batch": k}, x, y, group,
                          rounds=9)
        assert got == oracle, "bagged iter_batch=%s diverged" % k


def test_batched_dart_matches_oracle():
    """DART banked path: drop lotteries, 1/(1+k) shrinkages and
    normalization factors precompute host-side and feed the scan as
    stacked inputs; the f64 drop-factor replay must see the identical
    per-iteration history."""
    x, y, _ = _data_for("binary", 1600, seed=3)
    base = {**_params_for("binary"), "boosting_type": "dart"}
    oracle = _model_text({**base, "iter_batch": "1"}, x, y, rounds=10)
    for k in ("8", "3"):
        got = _model_text({**base, "iter_batch": k}, x, y, rounds=10)
        assert got == oracle, "dart iter_batch=%s diverged" % k


def test_batched_dart_bagged_matches_oracle():
    x, y, _ = _data_for("binary", 1600, seed=4)
    base = {**_params_for("binary"), "boosting_type": "dart",
            "bagging_fraction": 0.5, "bagging_freq": 2}
    oracle = _model_text({**base, "iter_batch": "1"}, x, y, rounds=8)
    got = _model_text({**base, "iter_batch": "8"}, x, y, rounds=8)
    assert got == oracle


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_batched_data_parallel_matches_oracle(objective):
    """tree_learner=data (single host, 8 virtual devices): the scan
    wraps the body INSIDE shard_map, so per-step psums stay put and
    the replicated [K, F] feature-mask specs cover the stacked xs.
    lambdarank rides its query-granular shard layout through the same
    wrapper (layout state is segment-constant, closed over)."""
    x, y, group = _data_for(objective, 2048, seed=7)
    base = {**_params_for(objective), "tree_learner": "data"}
    oracle = _model_text({**base, "iter_batch": "1"}, x, y, group,
                         rounds=6)
    got = _model_text({**base, "iter_batch": "4"}, x, y, group,
                      rounds=6)
    _assert_model_matches(got, oracle, objective, "data-parallel K=4")


def test_batched_ordered_reorder_scan_matches_oracle():
    """hist_reorder_every=1 makes EVERY iteration a re-sort, so the
    segment scans the REORDER body (bins/bag/gstate/row order ride the
    carry); cadence > 1 segments between re-sorts.  Pallas interpret
    mode exercises the real ordered-partition kernel path on CPU."""
    x, y, _ = _data_for("binary", 8192, seed=8)
    for every in ("1", "3"):
        base = {**_params_for("binary"), "hist_impl": "pallas",
                "hist_ordered": "auto", "hist_reorder_every": every}
        oracle = _model_text({**base, "iter_batch": "1"}, x, y, rounds=6)
        got = _model_text({**base, "iter_batch": "4"}, x, y, rounds=6)
        assert got == oracle, "reorder_every=%s diverged" % every


# ---------------------------------------------------------------------------
# boundaries: early stopping, metrics, checkpoints
# ---------------------------------------------------------------------------

def test_early_stopping_same_iteration():
    """Early stopping checks run every iteration in the reference, so
    an early-stop config forces K=1 segments — the stopped iteration
    and the saved model must match the oracle exactly."""
    x, y, _ = _data_for("binary", 1200, seed=2)
    xv, yv, _ = _data_for("binary", 400, seed=12)
    out = {}
    for k in ("1", "8"):
        params = {**_params_for("binary"), "metric": "binary_logloss",
                  "iter_batch": k}
        ds = lgb.Dataset(x, label=y)
        dv = lgb.Dataset(xv, label=yv, reference=ds)
        b = lgb.train(params, ds, num_boost_round=40, valid_sets=[dv],
                      early_stopping_rounds=3, verbose_eval=False)
        out[k] = (b.current_iteration, b.model_to_string())
    assert out["1"] == out["8"]


def test_metric_lines_unchanged(capsys):
    """metric_freq=2 with iter_batch=8: segments end at every metric
    boundary, so the logged metric lines (iteration numbers AND values)
    are identical to the oracle's."""
    x, y, _ = _data_for("binary", 1200, seed=6)
    xv, yv, _ = _data_for("binary", 400, seed=16)
    lines = {}
    for k in ("1", "8"):
        params = {**_params_for("binary"), "metric": "binary_logloss",
                  "metric_freq": 2, "iter_batch": k}
        ds = lgb.Dataset(x, label=y)
        dv = lgb.Dataset(xv, label=yv, reference=ds)
        capsys.readouterr()
        lgb.train(params, ds, num_boost_round=8, valid_sets=[dv],
                  verbose_eval=2)
        lines[k] = [ln for ln in capsys.readouterr().out.splitlines()
                    if "Iteration:" in ln]
    assert lines["1"] == lines["8"] and lines["1"]


def test_checkpoint_resume_mid_segment():
    """A checkpoint taken off the K grid (after 3 iters, iter_batch=8)
    resumes bit-for-bit: segment planning restarts from the restored
    absolute iteration, so the remaining segments retile without
    drifting any draw or boundary."""
    import tempfile

    x, y, _ = _data_for("binary", 1200, seed=9)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "min_data_in_leaf": 20, "metric": "",
              "bagging_fraction": 0.5, "bagging_freq": 2,
              "iter_batch": "8", "num_iterations": 8}
    ds = lgb.Dataset(x, label=y, params=params)

    def fresh(ib):
        cfg = Config.from_params({**{k: str(v) for k, v in
                                     params.items()}, "iter_batch": ib})
        inner = ds.inner
        obj = create_objective(cfg)
        obj.init(inner.metadata, inner.num_data)
        return create_boosting(cfg, inner, obj)

    ck = os.path.join(tempfile.mkdtemp(), "ibck.npz")
    a = fresh("8")
    done = 0
    while done < 3:
        _, k = a.train_segment(3 - done, is_eval=False)
        done += k
    a.save_checkpoint(ck)
    while done < 8:
        _, k = a.train_segment(8 - done, is_eval=False)
        done += k

    b = fresh("8")
    b.load_checkpoint(ck)
    done = b.iter
    while done < 8:
        _, k = b.train_segment(8 - done, is_eval=False)
        done += k

    # and the K=1 oracle end-to-end
    c = fresh("1")
    for _ in range(8):
        c.train_one_iter(None, None, False)

    ma, mb, mc = a.models, b.models, c.models
    assert len(ma) == len(mb) == len(mc) == 8
    for t1, t2, t3 in zip(ma, mb, mc):
        assert t1.to_string() == t2.to_string() == t3.to_string()


# ---------------------------------------------------------------------------
# segment scheduling (host logic, no training dispatch needed)
# ---------------------------------------------------------------------------

def _booster(extra=None, n=400, objective="binary"):
    x, y, group = _data_for(objective, n, seed=1)
    params = {**_params_for(objective), "min_data_in_leaf": 5,
              **(extra or {})}
    ds = lgb.Dataset(x, label=y, group=group,
                     params={k: str(v) for k, v in params.items()})
    cfg = Config.from_params({k: str(v) for k, v in params.items()})
    obj = create_objective(cfg)
    obj.init(ds.inner.metadata, ds.inner.num_data)
    return create_boosting(cfg, ds.inner, obj)

def test_plan_caps_at_rebag_boundary():
    g = _booster({"iter_batch": "8", "bagging_fraction": 0.5,
                  "bagging_freq": 3})
    assert g._plan_segment(100, is_eval=False) == 3
    g.iter = 2          # next re-bag at 3: one iteration left in epoch
    assert g._plan_segment(100, is_eval=False) == 1
    g.iter = 3          # ON the boundary: a full epoch fits
    assert g._plan_segment(100, is_eval=False) == 3


def test_plan_caps_at_metric_boundary_and_early_stop():
    g = _booster({"iter_batch": "8", "metric": "binary_logloss",
                  "metric_freq": 5})
    # no valid sets and no training metrics attached -> metrics inactive
    assert g._plan_segment(100, is_eval=True) == 8
    from lightgbm_tpu.metrics import create_metrics
    m = create_metrics(g.config)[0]
    m.init("training", g.train_data.metadata, g.train_data.num_data)
    g.training_metrics = [m]
    assert g._plan_segment(100, is_eval=True) == 5
    assert g._plan_segment(100, is_eval=False) == 8
    g.early_stopping_round = 2
    assert g._plan_segment(100, is_eval=True) == 1


def test_plan_remaining_and_disable():
    g = _booster({"iter_batch": "8"})
    assert g._plan_segment(3, is_eval=False) == 3
    assert g._plan_segment(100, is_eval=False) == 8
    g2 = _booster({"iter_batch": "1"})
    assert g2._plan_segment(100, is_eval=False) == 1


def test_auto_k_divides_metric_freq():
    g = _booster({"iter_batch": "auto", "metric": "binary_logloss",
                  "metric_freq": 6})
    # this suite runs on the CPU backend, where auto resolves to the
    # per-iteration oracle (gbdt._auto_iter_batch)
    assert g._auto_iter_batch() == 1
    # the accelerator policy: default 8, shrunk to the largest divisor
    # of metric_freq once metric output is live
    assert g._auto_iter_batch_accel() == 8     # metrics not attached yet
    from lightgbm_tpu.metrics import create_metrics
    m = create_metrics(g.config)[0]
    m.init("training", g.train_data.metadata, g.train_data.num_data)
    g.training_metrics = [m]
    assert g._auto_iter_batch_accel() == 6     # largest divisor of 6 <= 8
    g.config.metric_freq = 10
    assert g._auto_iter_batch_accel() == 5
    g.config.metric_freq = 1
    assert g._auto_iter_batch_accel() == 1


def test_iter_batch_config_validation():
    from lightgbm_tpu.utils.log import LightGBMError

    with pytest.raises(LightGBMError):
        Config.from_params({"iter_batch": "0"})
    with pytest.raises(LightGBMError):
        Config.from_params({"iter_batch": "bogus"})
    assert Config.from_params({"iter_batch": "4"}).iter_batch == "4"
    assert Config.from_params({}).iter_batch == "auto"


# ---------------------------------------------------------------------------
# real 2-process multi-host run
# ---------------------------------------------------------------------------

def test_multihost_batched_two_process(tmp_path):
    """2 jax processes x 4 virtual CPU devices run tree_learner=data
    through the MULTI-HOST fused sharded step with iter_batch=4 and
    iter_batch=1; ranks must agree and K=4 must reproduce the K=1
    model bytes."""
    import socket as socketlib
    import subprocess
    import sys

    rng = np.random.RandomState(0)
    n, ncol = 800, 5
    x = rng.randn(n, ncol)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(int)
    data = tmp_path / "train.tsv"
    data.write_text("\n".join(
        "\t".join([str(y[i])] + ["%f" % v for v in x[i]])
        for i in range(n)) + "\n")

    s = socketlib.socket()
    s.bind(("localhost", 0))
    port = str(s.getsockname()[1])
    s.close()

    outs = [str(tmp_path / ("model_%d" % r)) for r in range(2)]
    worker = os.path.join(os.path.dirname(__file__),
                          "mh_iterbatch_worker.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, worker, str(r), "2", port, str(data), outs[r]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    logs = [p.communicate(timeout=600)[0].decode() for p in procs]
    for r, p in enumerate(procs):
        assert p.returncode == 0, "worker %d failed:\n%s" % (r, logs[r])

    k1_0 = open(outs[0] + "_k1.txt").read()
    k4_0 = open(outs[0] + "_k4.txt").read()
    assert k1_0 == open(outs[1] + "_k1.txt").read(), \
        "ranks diverged (K=1)"
    assert k4_0 == open(outs[1] + "_k4.txt").read(), \
        "ranks diverged (K=4)"
    assert k4_0 == k1_0, "iter_batch=4 diverged from the K=1 oracle"
    assert "batched_segments=1" in logs[0]
