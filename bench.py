#!/usr/bin/env python
"""Headline benchmark: GBDT training wall-clock vs the reference CPU binary.

Workload: synthetic binary classification, N=1,000,000 rows x F=28 features
(the HIGGS shape at 1/11 scale), 100 trees, num_leaves=63, max_bin=255 —
the reference's own recommended settings (examples/binary_classification/
train.conf:29-57).

Both sides train on identical data on this host:
  - ours: lightgbm_tpu on the default JAX device (TPU when available),
    training-loop wall-clock measured after a 1-iteration warm-up booster
    has triggered XLA compilation (compile time reported separately in
    `compile_s`; it is a one-time per-shape cost).
  - baseline: the reference C++ binary (built from /root/reference into
    .ref_build/, never written back), training time taken from its own
    "N seconds elapsed, finished iteration 100" log line, which likewise
    excludes data loading.  The result is cached in .bench_cache/ keyed by
    workload + cpu count.

Prints ONE JSON line:
  {"metric": "train_steady_100trees_1Mx28", "value": <our seconds>,
   "unit": "s", "vs_baseline": <ref_seconds / our_seconds>, ...extras}
vs_baseline > 1 means we beat the reference.

Timing conventions (symmetric across every family): `*_wall_s` is the
raw loop wall-clock;
`*_train_s` is the chunked-steady extrapolation min(chunk) * chunks.
The emitted `vs_baseline_timing` map states which convention each
`vs_baseline` ratio uses (headline: wall; per-family ratios: steady;
predict: wall).
"""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(REPO, ".bench_cache")
REF_SRC = "/root/reference"
REF_BUILD = os.path.join(REPO, ".ref_build")

N_ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
N_FEAT = 28
NUM_TREES = 100
NUM_LEAVES = 63
MAX_BIN = 255
MIN_DATA_IN_LEAF = 100
LEARNING_RATE = 0.1
SEED = 42

# ranking micro-bench (device-path lambdarank, VERDICT r1 #6): synthetic
# LETOR-ish workload, fixed-size queries
RANK_DOCS = int(os.environ.get("BENCH_RANK_DOCS", 200_000))
RANK_QSIZE = 20
RANK_LEAVES = 31


def make_data():
    rng = np.random.RandomState(SEED)
    x = rng.randn(N_ROWS, N_FEAT).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
         + 0.3 * rng.randn(N_ROWS) > 0).astype(np.float32)
    return x, y


def holdout_data():
    rng = np.random.RandomState(SEED + 1)
    x = rng.randn(100_000, N_FEAT).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
         + 0.3 * rng.randn(100_000) > 0).astype(np.float32)
    return x, y


# iteration batching (config.iter_batch): the bench drives training
# through GBDT.train_segment like cli/api do, so the K-scan dispatch
# win is what gets measured; BENCH_ITER_BATCH=1 is the per-iteration
# oracle for A/B runs
ITER_BATCH = os.environ.get("BENCH_ITER_BATCH", "auto")
# trees for the instrumented dispatch/transfer probe (a short post-run
# pass on warm executables; 24 = 3 full auto-K segments + one deferred
# flush boundary)
PROBE_TREES = int(os.environ.get("BENCH_PROBE_TREES", 24))


def _drive(booster, n):
    """Segment-batched training loop: K iterations per device dispatch
    (train_segment), host sync only at flush boundaries — the same
    path the cli/api drivers run."""
    done = 0
    while done < n:
        _, k = booster.train_segment(n - done, is_eval=False)
        done += k


def _warm_n(booster, per, floor):
    """Warm-up length: with batching OFF (K=1 — e.g. iter_batch=auto on
    CPU) the historical two iterations cover the {reorder, plain}
    executables; with batching ON a FULL chunk is needed — the segment
    tiling dispatches several distinct lengths (steady K, re-sort K=1,
    remainders) and any executable not warmed compiles inside the timed
    loop.  chunks==1 families pay one extra chunk of training for that
    guarantee (cheap on accelerators, where batching is on)."""
    if booster._iter_batch_k() <= 1:
        return max(floor, 2)
    return max(floor, per)


def _params():
    return {
        "objective": "binary", "num_leaves": str(NUM_LEAVES),
        "max_bin": str(MAX_BIN), "min_data_in_leaf": str(MIN_DATA_IN_LEAF),
        "learning_rate": str(LEARNING_RATE), "metric": "",
        "iter_batch": ITER_BATCH,
    }


def build_dataset(cfg, x, y):
    from lightgbm_tpu.io.binning import find_bins
    from lightgbm_tpu.io.dataset import Dataset, Metadata

    rng = np.random.RandomState(SEED)
    sample = rng.choice(N_ROWS, min(50_000, N_ROWS), replace=False)
    mappers = find_bins(x[sample], len(sample), cfg.max_bin)
    bins = np.stack([m.value_to_bin(x[:, j]).astype(np.uint8)
                     for j, m in enumerate(mappers)])
    return Dataset(bins=bins, bin_mappers=mappers,
                   used_feature_map=np.arange(N_FEAT, dtype=np.int32),
                   real_feature_index=np.arange(N_FEAT, dtype=np.int32),
                   num_total_features=N_FEAT,
                   feature_names=["Column_%d" % i for i in range(N_FEAT)],
                   metadata=Metadata(label=y))


def run_ours():
    import jax
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    from lightgbm_tpu.analysis.guards import track_compiles
    from lightgbm_tpu.models.gbdt import dispatch_count

    x, y = make_data()
    cfg = Config.from_params(_params())

    t0 = time.time()
    ds = build_dataset(cfg, x, y)
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    booster = create_boosting(cfg, ds, obj)
    setup_s = time.time() - t0

    # warm-up: ONE FULL CHUNK on a throwaway booster triggers all XLA
    # compilations (cached by shape for the real run).  A whole chunk,
    # not two iterations: iteration batching tiles a chunk with several
    # distinct segment lengths (the steady K, the re-sort K=1 dispatch,
    # the between-resort remainder), and every one of those executables
    # must compile outside the timed loop.  The warm-up runs under
    # track_compiles so compile_s splits cold vs cache-warm: a prior
    # run of this shape leaves zero persistent-cache misses and
    # compile_s collapses to deserialization time.
    chunks = 4
    assert NUM_TREES % chunks == 0, "chunked timing needs chunks | NUM_TREES"
    per = NUM_TREES // chunks
    warm = create_boosting(cfg, ds, obj)
    t0 = time.time()
    with track_compiles() as cstats:
        _drive(warm, _warm_n(warm, per, 2))
        jax.block_until_ready(warm.scores)
    compile_s = time.time() - t0
    compile_cache = ("cache-warm" if cstats.cache_misses == 0
                     and cstats.cache_hits > 0 else
                     "cold" if cstats.cache_misses > 0 else "disabled")
    del warm

    # Time the loop in 4 chunks and report steady-state throughput
    # (min chunk x 4) as the headline, with the raw total alongside.
    t_all = time.time()
    chunk_s = []
    for _ in range(chunks):
        t0 = time.time()
        _drive(booster, per)
        jax.block_until_ready(booster.scores)
        float(np.asarray(booster.scores[0, 0]))  # force full completion
        chunk_s.append(time.time() - t0)
    train_total_s = time.time() - t_all
    train_s = min(chunk_s) * chunks

    # instrumented probe on warm executables: dispatches-per-tree and
    # device->host pulls for the training loop (the K-scan win as a
    # tracked metric, not a one-off) — guards count the explicit
    # device_get flushes, gbdt counts its own dispatches
    probe = create_boosting(cfg, ds, obj)
    d0 = dispatch_count()
    with track_compiles() as pstats:
        _drive(probe, PROBE_TREES)
        flushed = len(probe.models)    # materializes -> final device_get
    assert flushed == PROBE_TREES
    probe_dispatches = dispatch_count() - d0
    del probe

    model_path = os.path.join(CACHE, "bench_model.txt")
    booster.save_model_to_file(-1, True, model_path)

    xh, yh = holdout_data()
    pred = booster.predict(xh)[0]
    order = np.argsort(pred)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(len(pred))
    npos = yh.sum()
    auc = ((ranks[yh == 1].sum() - npos * (npos - 1) / 2)
           / (npos * (len(yh) - npos)))
    return {"train_s": train_s, "train_total_s": train_total_s,
            "compile_s": compile_s, "compile_cache": compile_cache,
            "compile_cache_hits": cstats.cache_hits,
            "compile_cache_misses": cstats.cache_misses,
            "setup_s": setup_s,
            "iter_batch": ITER_BATCH,
            "dispatches_per_tree": round(
                probe_dispatches / PROBE_TREES, 4),
            "device_gets_per_100_trees": round(
                pstats.device_gets * 100.0 / PROBE_TREES, 2),
            "probe_trees": PROBE_TREES,
            "auc": float(auc), "backend": jax.default_backend(),
            "model_path": model_path}


def make_rank_data():
    rng = np.random.RandomState(SEED + 7)
    x = rng.randn(RANK_DOCS, N_FEAT).astype(np.float32)
    rel = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.5 * rng.randn(RANK_DOCS)
    y = np.clip(np.round(rel + 1.5), 0, 4).astype(np.float32)
    qb = np.arange(0, RANK_DOCS + 1, RANK_QSIZE, dtype=np.int32)
    return x, y, qb


def _rank_params():
    return {
        "objective": "lambdarank", "num_leaves": str(RANK_LEAVES),
        "max_bin": str(MAX_BIN), "min_data_in_leaf": str(MIN_DATA_IN_LEAF),
        "learning_rate": str(LEARNING_RATE), "metric": "",
        "iter_batch": ITER_BATCH,
    }


def _run_rank_workload(prefix, extra_params=None, force_general=False):
    """One lambdarank training measurement.  prefix names the emitted
    keys (<prefix>_train_s steady, <prefix>_wall_s raw).  extra_params
    overlays _rank_params (e.g. tree_learner=data for the fused
    query-sharded step).  force_general=False keeps the objective's own
    routing; True clears row_shardable so tree_learner=data takes the
    pre-fusion general per-tree path — the fused-vs-general speedup
    pair for BASELINE.md."""
    import jax
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.binning import find_bins
    from lightgbm_tpu.io.dataset import Dataset, Metadata
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    x, y, qb = make_rank_data()
    cfg = Config.from_params({**_rank_params(), **(extra_params or {})})
    rng = np.random.RandomState(SEED)
    sample = rng.choice(RANK_DOCS, min(50_000, RANK_DOCS), replace=False)
    mappers = find_bins(x[sample], len(sample), cfg.max_bin)
    bins = np.stack([m.value_to_bin(x[:, j]).astype(np.uint8)
                     for j, m in enumerate(mappers)])
    md = Metadata(label=y, query_boundaries=qb)
    ds = Dataset(bins=bins, bin_mappers=mappers,
                 used_feature_map=np.arange(N_FEAT, dtype=np.int32),
                 real_feature_index=np.arange(N_FEAT, dtype=np.int32),
                 num_total_features=N_FEAT,
                 feature_names=["Column_%d" % i for i in range(N_FEAT)],
                 metadata=md)

    def fresh():
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        if force_general:
            obj.row_shardable = False
        return create_boosting(cfg, ds, obj)

    # ONE-CHUNK warm-up, same reason as the binary family (run_ours):
    # iteration batching tiles a chunk with several distinct segment
    # lengths (reorder K=1, the steady K, remainders) and every one
    # must compile outside the timed loop
    chunks = 4
    per = NUM_TREES // chunks
    warm = fresh()
    _drive(warm, _warm_n(warm, per, 2))
    jax.block_until_ready(warm.scores)
    del warm

    booster = fresh()
    # chunked min*chunks steady timing, like every other family
    chunk_s = []
    t_all = time.time()
    for _ in range(chunks):
        t0 = time.time()
        _drive(booster, per)
        jax.block_until_ready(booster.scores)
        float(np.asarray(booster.scores[0, 0]))
        chunk_s.append(time.time() - t0)
    return {prefix + "_train_s": min(chunk_s) * chunks,
            prefix + "_wall_s": time.time() - t_all}


def run_ours_rank():
    return _run_rank_workload("rank")


def run_reference_rank():
    ncpu = os.cpu_count()
    key = "refrank_%dx%d_q%d_t%d_l%d_b%d_cpu%d.json" % (
        RANK_DOCS, N_FEAT, RANK_QSIZE, NUM_TREES, RANK_LEAVES, MAX_BIN, ncpu)
    cache_f = os.path.join(CACHE, key)
    if os.path.exists(cache_f):
        with open(cache_f) as f:
            return json.load(f)

    exe = ensure_ref_binary()
    os.makedirs(CACHE, exist_ok=True)
    train_file = os.path.join(CACHE, "bench_rank_%d.train" % RANK_DOCS)
    if not os.path.exists(train_file):
        x, y, qb = make_rank_data()
        np.savetxt(train_file, np.concatenate([y[:, None], x], axis=1),
                   fmt="%.6g", delimiter="\t")
        with open(train_file + ".query", "w") as f:
            for i in range(len(qb) - 1):
                f.write("%d\n" % (qb[i + 1] - qb[i]))
    out = subprocess.run(
        [exe, "task=train", "data=" + train_file, "objective=lambdarank",
         "num_trees=%d" % NUM_TREES, "num_leaves=%d" % RANK_LEAVES,
         "max_bin=%d" % MAX_BIN, "min_data_in_leaf=%d" % MIN_DATA_IN_LEAF,
         "learning_rate=%g" % LEARNING_RATE, "metric=",
         "is_save_binary_file=false", "output_model=/dev/null"],
        capture_output=True, text=True, cwd=CACHE, check=True)
    last = None
    for line in out.stdout.splitlines():
        m = re.search(r"([\d.]+) seconds elapsed, finished iteration (\d+)",
                      line)
        if m:
            last = (float(m.group(1)), int(m.group(2)))
    if last is None or last[1] != NUM_TREES:
        raise RuntimeError("could not parse reference rank timing:\n"
                           + out.stdout)
    res = {"ref_rank_train_s": last[0], "ncpu": ncpu}
    with open(cache_f, "w") as f:
        json.dump(res, f)
    return res


def _measure_bagged(cfg, ds, prefix, num_trees=NUM_TREES, warm_iters=6):
    """One bagged training measurement with the symmetric reporting
    every other family gets: <prefix>_steady_s (min(chunk) * chunks),
    <prefix>_wall_s (raw loop) and <prefix>_compile_s (warm-up wall —
    compile or persistent-cache load).  warm_iters must span one
    re-bagging boundary so the re-bag mask plumbing (and under
    bag_compact the in-bag-first arrangement dispatch) compiles outside
    the timed loop."""
    import jax
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    def fresh():
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        return create_boosting(cfg, ds, obj)

    # iteration batching slices a bag epoch into {K = freq} segments
    # (plus reorder/remainder dispatches under ordered mode); warm one
    # full chunk so every segment executable compiles outside the loop
    freq = max(int(cfg.bagging_freq), 1)
    chunks = 4 if num_trees % (4 * freq) == 0 else 1
    per = num_trees // chunks
    warm = fresh()
    t0 = time.time()
    # a full chunk under batching (the bag/reorder boundary offsets
    # produce several distinct segment lengths, and any remainder
    # executable not warmed here would compile inside the timed loop);
    # the historical warm_iters with batching off
    _drive(warm, _warm_n(warm, per, warm_iters))
    jax.block_until_ready(warm.scores)
    compile_s = time.time() - t0
    del warm
    booster = fresh()
    # chunked min*chunks steady timing like every family; chunking
    # requires
    # each chunk to span WHOLE bagging_freq re-bag cycles, else chunks
    # carry unequal re-bag/arrange dispatch counts and min(chunk)*chunks
    # underestimates steady time
    chunk_s = []
    t_all = time.time()
    for _ in range(chunks):
        t0 = time.time()
        _drive(booster, per)
        jax.block_until_ready(booster.scores)
        float(np.asarray(booster.scores[0, 0]))
        chunk_s.append(time.time() - t0)
    return {prefix + "_steady_s": min(chunk_s) * chunks,
            prefix + "_wall_s": time.time() - t_all,
            prefix + "_compile_s": round(compile_s, 3)}


def run_ours_bagged():
    """Bagged + feature-fraction run (VERDICT r2 #3): exercises the
    packed-mask upload, the device stopped-flag deferral, and (round 9)
    the bag-compacted fused step when bag_compact engages."""
    from lightgbm_tpu.config import Config

    x, y = make_data()
    cfg = Config.from_params({**_params(), "bagging_fraction": "0.8",
                              "bagging_freq": "5",
                              "feature_fraction": "0.8"})
    ds = build_dataset(cfg, x, y)
    res = _measure_bagged(cfg, ds, "bagged")
    # continuity key: earlier rounds' BASELINE entries read bagged_train_s
    res["bagged_train_s"] = res["bagged_steady_s"]
    return res


# bagging_fraction sweep (0.25 / 0.5 / 0.8, compact vs masked): the
# machine-checked scaling claim — bagged histogram work should track the
# fraction under bag_compact, not stay flat at the full-N sweep cost
SWEEP_TREES = int(os.environ.get("BENCH_SWEEP_TREES", 40))


def run_bagged_sweep():
    """Per-fraction steady times with bag_compact on vs off on identical
    data/bins, plus the on/off speedup — recorded in BENCH_*.json so the
    'histogram work scales with bagging_fraction' claim is checked every
    round."""
    from lightgbm_tpu.config import Config

    x, y = make_data()
    base = Config.from_params(_params())
    ds = build_dataset(base, x, y)
    out = {}
    for frac in ("0.25", "0.5", "0.8"):
        times = {}
        for mode in ("on", "off"):
            cfg = Config.from_params({
                **_params(), "bagging_fraction": frac,
                "bagging_freq": "5", "bag_compact": mode})
            res = _measure_bagged(cfg, ds, "tmp", num_trees=SWEEP_TREES)
            times[mode] = res["tmp_steady_s"]
            key = "bag_sweep_f%s_%s" % (
                frac, "compact" if mode == "on" else "masked")
            out[key + "_steady_s"] = round(res["tmp_steady_s"], 3)
        out["bag_sweep_f%s_compact_speedup" % frac] = round(
            times["off"] / times["on"], 4)
    out["bag_sweep_trees"] = SWEEP_TREES
    return out


def run_reference_bagged():
    return _run_reference_binary(
        ["objective=binary", "bagging_fraction=0.8", "bagging_freq=5",
         "feature_fraction=0.8"],
        "refbag_%dx%d_t%d_l%d_b%d_cpu%d.json" % (
            N_ROWS, N_FEAT, NUM_TREES, NUM_LEAVES, MAX_BIN, os.cpu_count()),
        "ref_bagged_train_s")


def run_predict_e2e(model_path):
    """task=predict file-to-file, both sides including parse + predict +
    format over the SAME 1M-row TSV (VERDICT r2 #6; reference
    predictor.hpp:82-130)."""
    exe = ensure_ref_binary()
    train_file = os.path.join(CACHE, "bench_%d.train" % N_ROWS)
    if not os.path.exists(train_file):
        x, y = make_data()
        np.savetxt(train_file, np.concatenate([y[:, None], x], axis=1),
                   fmt="%.6g", delimiter="\t")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    ours_out = os.path.join(CACHE, "bench_pred_ours.txt")
    # min of 2, like the chunked steady-state training timing
    ours_s = float("inf")
    for _ in range(2):
        t0 = time.time()
        # the shipped CLI launcher (repo-root `lightgbm`, the analog of
        # the reference's binary); predict is host-only.  PYTHON pins
        # the launcher to this very interpreter.
        env["PYTHON"] = sys.executable
        subprocess.run(
            [os.path.join(REPO, "lightgbm"), "task=predict",
             "data=" + train_file, "input_model=" + model_path,
             "output_result=" + ours_out],
            capture_output=True, text=True, check=True, env=env, cwd=CACHE)
        ours_s = min(ours_s, time.time() - t0)
    ref_out = os.path.join(CACHE, "bench_pred_ref.txt")
    t0 = time.time()
    subprocess.run(
        [exe, "task=predict", "data=" + train_file,
         "input_model=" + model_path, "output_result=" + ref_out],
        capture_output=True, text=True, check=True, cwd=CACHE)
    ref_s = time.time() - t0
    return {"predict_e2e_s": round(ours_s, 3),
            "ref_predict_e2e_s": round(ref_s, 3),
            "predict_vs_baseline": round(ref_s / ours_s, 4)}


# -- task=serve closed-loop benchmark (serving/ tentpole) ---------------

SERVE_CLIENTS = int(os.environ.get("BENCH_SERVE_CLIENTS", 16))
SERVE_REQS = int(os.environ.get("BENCH_SERVE_REQS", 150))
SERVE_ROWS_PER_REQ = int(os.environ.get("BENCH_SERVE_ROWS", 4))
SERVE_TREES = 100
SERVE_LEAVES = 63


def _serve_model_text(num_trees=SERVE_TREES, num_leaves=SERVE_LEAVES,
                      num_feat=N_FEAT, seed=11):
    """Synthetic balanced forest in the reference text format: the
    serving bench needs a bench-shaped model (100 trees x 63 leaves)
    without paying a training run."""
    rng = np.random.RandomState(seed)
    out = ["gbdt", "num_class=1", "label_index=0",
           "max_feature_idx=%d" % (num_feat - 1), "sigmoid=1",
           "objective=binary", ""]
    for t in range(num_trees):
        nl = num_leaves
        sf = np.zeros(nl - 1, dtype=np.int64)
        thr = np.zeros(nl - 1)
        lc = np.zeros(nl - 1, dtype=np.int64)
        rc = np.zeros(nl - 1, dtype=np.int64)
        state = {"node": 0, "leaf": 0}

        def build(k):
            if k == 1:
                leaf = state["leaf"]
                state["leaf"] += 1
                return ~leaf
            i = state["node"]
            state["node"] += 1
            sf[i] = rng.randint(num_feat)
            thr[i] = rng.randn()
            left = build(k // 2)
            right = build(k - k // 2)
            lc[i], rc[i] = left, right
            return i

        build(nl)
        lv = rng.randn(nl) * 0.05
        out += ["Tree=%d" % t,
                "num_leaves=%d" % nl,
                "split_feature=" + " ".join(str(v) for v in sf),
                "split_gain=" + " ".join("1" for _ in sf),
                "threshold=" + " ".join("%g" % v for v in thr),
                "left_child=" + " ".join(str(v) for v in lc),
                "right_child=" + " ".join(str(v) for v in rc),
                "leaf_parent=" + " ".join("0" for _ in range(nl)),
                "leaf_value=" + " ".join("%g" % v for v in lv),
                "internal_value=" + " ".join("0" for _ in sf),
                ""]
    out += ["feature importance:", ""]
    return "\n".join(out)


def _spawn_serve(params, log_name="bench_serve_server.log"):
    """Start a task=serve subprocess on a fresh port and wait for
    /healthz.  Returns (proc, port, log_f); stop with _stop_serve.
    Shared by the closed-loop round driver and the open-loop leg of the
    worker-scaling sweep so the spawn/readiness logic cannot drift."""
    import http.client
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # log to a file, not a PIPE: nothing drains a pipe during the run,
    # so a chatty server would fill it and block mid-benchmark
    log_path = os.path.join(CACHE, log_name)
    log_f = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "task=serve",
         "serve_port=%d" % port, *params],
        env=env, stdout=log_f, stderr=subprocess.STDOUT, text=True)
    deadline = time.time() + 120
    while True:
        try:
            c = http.client.HTTPConnection("127.0.0.1", port,
                                           timeout=5)
            c.request("GET", "/healthz")
            if c.getresponse().read():
                c.close()
                return proc, port, log_f
        except OSError:
            if proc.poll() is not None or time.time() > deadline:
                log_f.flush()
                with open(log_path) as lf:
                    tail = lf.read()[-2000:]
                _stop_serve(proc, log_f)
                raise RuntimeError(
                    "serve process did not come up:\n" + tail)
            time.sleep(0.1)


def _stop_serve(proc, log_f):
    import signal as sig
    proc.send_signal(sig.SIGTERM)
    try:
        proc.wait(30)
    except subprocess.TimeoutExpired:
        proc.kill()
    log_f.close()


def _serve_round(port_params, bodies, warm_reqs=10):
    """Start a task=serve subprocess, drive SERVE_CLIENTS closed-loop
    client threads (1-row requests, keep-alive), return
    (latencies_s, responses_per_client, wall_s)."""
    import http.client
    import socket
    import threading

    proc, port, log_f = _spawn_serve(port_params)
    try:
        lat = [[] for _ in range(SERVE_CLIENTS)]
        resp = [set() for _ in range(SERVE_CLIENTS)]
        errs = []

        def client(ci):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                conn.connect()
                # headers and body go out as two writes; without
                # TCP_NODELAY Nagle holds the second for the delayed ACK
                conn.sock.setsockopt(socket.IPPROTO_TCP,
                                     socket.TCP_NODELAY, 1)
                body = bodies[ci % len(bodies)]
                for _ in range(warm_reqs):
                    conn.request("POST", "/predict", body)
                    conn.getresponse().read()
                for _ in range(SERVE_REQS):
                    t0 = time.monotonic()
                    conn.request("POST", "/predict", body)
                    out = conn.getresponse().read()
                    lat[ci].append(time.monotonic() - t0)
                    resp[ci].add(out)
                conn.close()
            except Exception as ex:
                errs.append(ex)

        ts = [threading.Thread(target=client, args=(ci,))
              for ci in range(SERVE_CLIENTS)]
        t_all = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        wall = time.time() - t_all
        if errs:
            raise RuntimeError("serve clients failed: %r" % errs[:3])
        return [v for ls in lat for v in ls], resp, wall
    finally:
        _stop_serve(proc, log_f)


def run_serving_bench():
    """Closed-loop task=serve throughput + latency, micro-batching ON
    vs batch-size-1 dispatch (serve_max_batch_rows=1), same clients,
    byte-equal responses required."""
    os.makedirs(CACHE, exist_ok=True)
    model = os.path.join(CACHE, "bench_serve_model.txt")
    if not os.path.exists(model):
        with open(model, "w") as f:
            f.write(_serve_model_text())
    rng = np.random.RandomState(SEED + 9)
    bodies = []
    for _ in range(SERVE_CLIENTS):
        rows = rng.randn(SERVE_ROWS_PER_REQ, N_FEAT)
        bodies.append("".join(
            "0\t" + "\t".join("%.6g" % v for v in row) + "\n"
            for row in rows).encode())
    common = ["input_model=" + model, "metric_freq=100", "verbose=0"]
    lat_b, resp_b, wall_b = _serve_round(
        common + ["serve_max_batch_rows=4096",
                  "serve_batch_timeout_ms=2"], bodies)
    lat_1, resp_1, wall_1 = _serve_round(
        common + ["serve_max_batch_rows=1",
                  "serve_batch_timeout_ms=0"], bodies)
    # equal correctness: every client saw EXACTLY one distinct response
    # per mode, and the same bytes in both modes
    for ci in range(SERVE_CLIENTS):
        assert len(resp_b[ci]) == 1 and resp_b[ci] == resp_1[ci], \
            "serving responses diverged between batching modes"
    n = SERVE_CLIENTS * SERVE_REQS * SERVE_ROWS_PER_REQ
    lat_b.sort()
    lat_1.sort()
    return {
        "serve_rows_per_s": round(n / wall_b, 1),
        "serve_p50_ms": round(lat_b[len(lat_b) // 2] * 1e3, 3),
        "serve_p99_ms": round(lat_b[int(len(lat_b) * 0.99)] * 1e3, 3),
        "serve_batch1_rows_per_s": round(n / wall_1, 1),
        "serve_batch1_p50_ms": round(lat_1[len(lat_1) // 2] * 1e3, 3),
        "serve_batch1_p99_ms": round(lat_1[int(len(lat_1) * 0.99)] * 1e3,
                                     3),
        "serve_batch_speedup": round(wall_1 / wall_b, 4),
        "serve_clients": SERVE_CLIENTS,
        "serve_rows_per_req": SERVE_ROWS_PER_REQ,
    }


SERVE_WORKER_SWEEP = [int(w) for w in os.environ.get(
    "BENCH_SERVE_WORKERS", "1,4,8").split(",") if w.strip()]
SERVE_OPEN_RPS = int(os.environ.get("BENCH_SERVE_RPS", 150))
SERVE_OPEN_SECS = float(os.environ.get("BENCH_SERVE_OPEN_SECS", 5))


def _serve_open_loop(port, bodies, want, rps, duration):
    """Open-loop fixed-RPS load: requests fire on a fixed schedule
    regardless of completions (no coordinated omission — a stalled
    server cannot slow the arrival rate), latency measured from each
    request's SCHEDULED send time.  Byte-equal responses REQUIRED.
    Returns sorted latencies (s) and the count that missed schedule by
    > 1 s (overload indicator)."""
    import http.client
    import socket
    import threading

    n = max(1, int(rps * duration))
    nthreads = min(64, max(8, rps // 5))
    lat = [[] for _ in range(nthreads)]
    errs = []
    t0 = time.monotonic() + 0.25   # everyone agrees on the schedule

    def sender(tid):
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            conn.connect()
            conn.sock.setsockopt(socket.IPPROTO_TCP,
                                 socket.TCP_NODELAY, 1)
            for i in range(tid, n, nthreads):
                sched = t0 + i / rps
                now = time.monotonic()
                if sched > now:
                    time.sleep(sched - now)
                conn.request("POST", "/predict",
                             bodies[i % len(bodies)])
                out = conn.getresponse().read()
                done = time.monotonic()
                if out != want[i % len(bodies)]:
                    raise RuntimeError(
                        "open-loop response bytes diverged")
                lat[tid].append(done - sched)
            conn.close()
        except Exception as ex:
            errs.append(ex)

    ts = [threading.Thread(target=sender, args=(tid,))
          for tid in range(nthreads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise RuntimeError("open-loop clients failed: %r" % errs[:3])
    flat = sorted(v for ls in lat for v in ls)
    lagged = sum(1 for v in flat if v > 1.0)
    return flat, lagged


def run_serving_scale_bench():
    """Worker-scaling serving bench (serving/frontend.py): closed-loop
    throughput AND open-loop fixed-RPS p50/p99 at serve_workers in
    SERVE_WORKER_SWEEP, byte-equal responses required everywhere.  The
    1-worker row is the single-process PR 2 server (the acceptance
    baseline for the >= 3x-at-8-workers target)."""
    os.makedirs(CACHE, exist_ok=True)
    model = os.path.join(CACHE, "bench_serve_model.txt")
    if not os.path.exists(model):
        with open(model, "w") as f:
            f.write(_serve_model_text())
    rng = np.random.RandomState(SEED + 13)
    bodies = []
    for _ in range(SERVE_CLIENTS):
        rows = rng.randn(SERVE_ROWS_PER_REQ, N_FEAT)
        bodies.append("".join(
            "0\t" + "\t".join("%.6g" % v for v in row) + "\n"
            for row in rows).encode())
    common = ["input_model=" + model, "metric_freq=100", "verbose=0",
              "serve_max_batch_rows=4096", "serve_batch_timeout_ms=2"]
    out = {"serve_worker_sweep": SERVE_WORKER_SWEEP,
           "serve_open_rps": SERVE_OPEN_RPS,
           "serve_ncpu": os.cpu_count()}
    want_resp = None
    base_rows_per_s = None
    for workers in SERVE_WORKER_SWEEP:
        params = common + ["serve_workers=%d" % workers]
        lat, resp, wall = _serve_round(params, bodies)
        # byte parity ACROSS worker counts: every client's single
        # distinct response must match the 1-worker run's
        flat = [next(iter(r)) for r in resp]
        assert all(len(r) == 1 for r in resp), \
            "responses diverged within a worker sweep round"
        if want_resp is None:
            want_resp = flat
        assert flat == want_resp, \
            "responses diverged across worker counts"
        n = SERVE_CLIENTS * SERVE_REQS * SERVE_ROWS_PER_REQ
        rows_per_s = n / wall
        if base_rows_per_s is None:
            base_rows_per_s = rows_per_s
        lat.sort()
        tag = "serve_w%d" % workers
        out[tag + "_rows_per_s"] = round(rows_per_s, 1)
        out[tag + "_closed_p50_ms"] = round(
            lat[len(lat) // 2] * 1e3, 3)
        out[tag + "_closed_p99_ms"] = round(
            lat[int(len(lat) * 0.99)] * 1e3, 3)
        out[tag + "_scaling_vs_1"] = round(
            rows_per_s / base_rows_per_s, 3)
        # open-loop leg against the SAME server configuration
        proc, port, log_f = _spawn_serve(
            params, log_name="bench_serve_open.log")
        try:
            open_lat, lagged = _serve_open_loop(
                port, bodies, want_resp, SERVE_OPEN_RPS,
                SERVE_OPEN_SECS)
            out[tag + "_open_p50_ms"] = round(
                open_lat[len(open_lat) // 2] * 1e3, 3)
            out[tag + "_open_p99_ms"] = round(
                open_lat[int(len(open_lat) * 0.99)] * 1e3, 3)
            out[tag + "_open_lagged"] = lagged
        finally:
            _stop_serve(proc, log_f)
    if len(SERVE_WORKER_SWEEP) > 1:
        last = SERVE_WORKER_SWEEP[-1]
        out["serve_worker_speedup"] = \
            out["serve_w%d_rows_per_s" % last] / base_rows_per_s
    return out


SERVE_LOWLAT_RPS = [int(r) for r in os.environ.get(
    "BENCH_SERVE_LOWLAT_RPS", "40,400").split(",") if r.strip()]
SERVE_FLEET_N = int(os.environ.get("BENCH_SERVE_FLEET_MODELS", 64))


def run_serving_lowlat_bench():
    """The low-latency lane's headline: open-loop fixed-RPS SINGLE-ROW
    latency with serve_low_latency on vs off, same bodies, byte-equal
    responses required across the lanes.  At low RPS the off-server
    pays the coalescing window on nearly every request; the lane
    answers synchronously, so its p50/p99 measure the actual descend+
    format cost."""
    import urllib.request

    os.makedirs(CACHE, exist_ok=True)
    model = os.path.join(CACHE, "bench_serve_model.txt")
    if not os.path.exists(model):
        with open(model, "w") as f:
            f.write(_serve_model_text())
    rng = np.random.RandomState(SEED + 17)
    bodies = []
    for _ in range(32):
        row = rng.randn(1, N_FEAT)[0]
        bodies.append(("0\t" + "\t".join("%.6g" % v for v in row)
                       + "\n").encode())
    # the low-latency tier's shipped shape is the jax-free native
    # process (the single-row fast path): both legs run it so the A-B
    # isolates the ADMISSION decision, not the engine
    common = ["input_model=" + model, "metric_freq=100", "verbose=0",
              "serve_backend=native",
              "serve_max_batch_rows=4096", "serve_batch_timeout_ms=2"]
    out = {"serve_lowlat_rps_sweep": SERVE_LOWLAT_RPS}
    want = None
    for lane in ("off", "on"):
        proc, port, log_f = _spawn_serve(
            common + ["serve_low_latency=%s" % lane],
            log_name="bench_serve_lane_%s.log" % lane)
        try:
            got = []
            for b in bodies:
                req = urllib.request.Request(
                    "http://127.0.0.1:%d/predict" % port, data=b)
                with urllib.request.urlopen(req, timeout=60) as r:
                    got.append(r.read())
            if want is None:
                want = got
            # lane routing must never change a response byte
            assert got == want, \
                "lane %s responses diverged from lane-off bytes" % lane
            # sequential closed-loop leg: one keep-alive client, the
            # cleanest single-row number (no client-side contention) —
            # the lane-off row pays the coalescing window every time
            import http.client
            import socket
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            conn.connect()
            conn.sock.setsockopt(socket.IPPROTO_TCP,
                                 socket.TCP_NODELAY, 1)
            seq = []
            for i in range(260):
                t0 = time.monotonic()
                conn.request("POST", "/predict",
                             bodies[i % len(bodies)])
                conn.getresponse().read()
                seq.append(time.monotonic() - t0)
            conn.close()
            seq = sorted(seq[10:])    # drop the warm-up head
            out["serve_lane_%s_seq_p50_ms" % lane] = round(
                seq[len(seq) // 2] * 1e3, 3)
            out["serve_lane_%s_seq_p99_ms" % lane] = round(
                seq[int(len(seq) * 0.99)] * 1e3, 3)
            for rps in SERVE_LOWLAT_RPS:
                lat, lagged = _serve_open_loop(
                    port, bodies, want, rps, SERVE_OPEN_SECS)
                tag = "serve_lane_%s_rps%d" % (lane, rps)
                out[tag + "_p50_ms"] = round(
                    lat[len(lat) // 2] * 1e3, 3)
                out[tag + "_p99_ms"] = round(
                    lat[int(len(lat) * 0.99)] * 1e3, 3)
                out[tag + "_lagged"] = lagged
        finally:
            _stop_serve(proc, log_f)
    for rps in SERVE_LOWLAT_RPS:
        off = out["serve_lane_off_rps%d_p99_ms" % rps]
        on = out["serve_lane_on_rps%d_p99_ms" % rps]
        out["serve_lane_p99_gain_rps%d" % rps] = \
            round(off / on, 3) if on > 0 else None
    if out.get("serve_lane_on_seq_p50_ms"):
        out["serve_lane_seq_p50_gain"] = round(
            out["serve_lane_off_seq_p50_ms"]
            / out["serve_lane_on_seq_p50_ms"], 3)
    return out


def run_serving_fleet_bench():
    """Fleet scale-out sweep: SERVE_FLEET_N registered models through a
    16-slot warm pool.  Warm-hit throughput must stay in family with
    the single-model server (the pool adds a dict hop, not a load),
    and cold-hit latency — a full parse + lazy warm on the request
    path — stays bounded because device-bucket compiles are deferred."""
    import urllib.parse
    import urllib.request

    os.makedirs(CACHE, exist_ok=True)
    fdir = os.path.join(CACHE, "bench_fleet_models")
    os.makedirs(fdir, exist_ok=True)
    base = _serve_model_text()
    models = []
    for i in range(SERVE_FLEET_N):
        p = os.path.join(fdir, "m%03d.txt" % i)
        if not os.path.exists(p):
            with open(p, "w") as f:
                f.write(base)
        models.append(p)
    rng = np.random.RandomState(SEED + 19)
    row = rng.randn(1, N_FEAT)[0]
    body = ("0\t" + "\t".join("%.6g" % v for v in row) + "\n").encode()
    pool = 16
    params = ["input_model=" + models[0],
              "serve_models=" + ",".join(models[1:pool]),
              "serve_fleet_max_models=%d" % pool,
              "metric_freq=100", "verbose=0",
              "serve_max_batch_rows=4096", "serve_batch_timeout_ms=2"]
    proc, port, log_f = _spawn_serve(params,
                                     log_name="bench_serve_fleet.log")
    try:
        def post_model(path):
            q = ("?model=" + urllib.parse.quote(path, safe="")) \
                if path else ""
            t0 = time.monotonic()
            req = urllib.request.Request(
                "http://127.0.0.1:%d/predict%s" % (port, q), data=body)
            with urllib.request.urlopen(req, timeout=120) as r:
                out_b = r.read()
            return time.monotonic() - t0, out_b

        # register the cold tail through the deploy-push /reload shape
        # ({"model":.., "default": false}) so cold hits are exercised
        # via ?model=
        for p in models[pool:]:
            req = urllib.request.Request(
                "http://127.0.0.1:%d/reload" % port,
                data=json.dumps({"model": p,
                                 "default": False}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=120).read()
        # warm-hit phase: round-robin the resident models
        warm_paths = models[:pool]
        for p in warm_paths:          # touch once: everyone resident
            post_model(p)
        n_warm = 300
        t0 = time.monotonic()
        warm_lat = []
        want = {}
        for i in range(n_warm):
            p = warm_paths[i % len(warm_paths)]
            dt, got = post_model(p)
            warm_lat.append(dt)
            if p in want:
                assert want[p] == got, "warm-hit bytes diverged"
            want[p] = got
        warm_wall = time.monotonic() - t0
        # single-model control on the same server: default model only
        t0 = time.monotonic()
        for _ in range(n_warm):
            post_model(None)
        single_wall = time.monotonic() - t0
        # cold-hit phase: churn ALL models through the 16-slot pool —
        # every request past the pool is a parse + lazy warm
        cold_lat = []
        for sweep in range(2):
            for p in models:
                dt, _ = post_model(p)
                cold_lat.append(dt)
        warm_lat.sort()
        cold_lat.sort()
        return {
            "serve_fleet_models": SERVE_FLEET_N,
            "serve_fleet_pool": pool,
            "serve_fleet_warm_rps": round(n_warm / warm_wall, 1),
            "serve_fleet_single_rps": round(n_warm / single_wall, 1),
            "serve_fleet_warm_vs_single": round(
                single_wall / warm_wall, 3),
            "serve_fleet_warm_p99_ms": round(
                warm_lat[int(len(warm_lat) * 0.99)] * 1e3, 3),
            "serve_fleet_cold_p50_ms": round(
                cold_lat[len(cold_lat) // 2] * 1e3, 3),
            "serve_fleet_cold_p99_ms": round(
                cold_lat[int(len(cold_lat) * 0.99)] * 1e3, 3),
        }
    finally:
        _stop_serve(proc, log_f)


def ensure_ref_binary():
    exe = os.path.join(REF_BUILD, "ref_src", "lightgbm")
    if os.path.exists(exe):
        return exe
    os.makedirs(REF_BUILD, exist_ok=True)
    src_copy = os.path.join(REF_BUILD, "ref_src")
    if not os.path.exists(src_copy):
        subprocess.run(["cp", "-r", REF_SRC, src_copy], check=True)
        subprocess.run(["rm", "-rf", os.path.join(src_copy, ".git")],
                       check=True)
    bdir = os.path.join(REF_BUILD, "build")
    os.makedirs(bdir, exist_ok=True)
    subprocess.run(["cmake", src_copy, "-DCMAKE_BUILD_TYPE=Release"],
                   cwd=bdir, check=True, capture_output=True)
    subprocess.run(["make", "-j8"], cwd=bdir, check=True,
                   capture_output=True)
    return exe


def _run_reference_binary(extra_args, key, field, train_file=None,
                          num_trees=NUM_TREES, metric=""):
    """Reference binary training seconds (cached per workload+host).
    extra_args must include the objective; train_file defaults to the
    shared binary-label file.  `metric` must name a compatible metric
    for objectives whose Config rejects the empty default (multiclass);
    with no valid files it is never evaluated, so timing is unaffected."""
    cache_f = os.path.join(CACHE, key)
    if os.path.exists(cache_f):
        with open(cache_f) as f:
            return json.load(f)

    exe = ensure_ref_binary()
    os.makedirs(CACHE, exist_ok=True)
    if train_file is None:
        train_file = os.path.join(CACHE, "bench_%d.train" % N_ROWS)
        if not os.path.exists(train_file):
            x, y = make_data()
            np.savetxt(train_file, np.concatenate([y[:, None], x], axis=1),
                       fmt="%.6g", delimiter="\t")
    # min of 2 fresh runs: host CPU state swung a cached single sample
    # 29.2 s -> 14.9 s across sessions (VERDICT r2 weak #5); the best
    # observed run is the fairest steady-state stand-in for both sides
    best = None
    for _ in range(2):
        out = subprocess.run(
            [exe, "task=train", "data=" + train_file,
             "num_trees=%d" % num_trees, "num_leaves=%d" % NUM_LEAVES,
             "max_bin=%d" % MAX_BIN,
             "min_data_in_leaf=%d" % MIN_DATA_IN_LEAF,
             "learning_rate=%g" % LEARNING_RATE, "metric=%s" % metric,
             "is_save_binary_file=false", "output_model=/dev/null",
             *extra_args],
            capture_output=True, text=True, cwd=CACHE, check=True)
        last = None
        for line in out.stdout.splitlines():
            m = re.search(
                r"([\d.]+) seconds elapsed, finished iteration (\d+)",
                line)
            if m:
                last = (float(m.group(1)), int(m.group(2)))
        if last is None or last[1] != num_trees:
            raise RuntimeError("could not parse reference timing:\n"
                               + out.stdout)
        best = last[0] if best is None else min(best, last[0])
    res = {field: best, "ncpu": os.cpu_count()}
    with open(cache_f, "w") as f:
        json.dump(res, f)
    return res


def run_reference():
    return _run_reference_binary(
        ["objective=binary"], "ref_%dx%d_t%d_l%d_b%d_cpu%d.json" % (
            N_ROWS, N_FEAT, NUM_TREES, NUM_LEAVES, MAX_BIN,
            os.cpu_count()), "ref_train_s")


# -- regression / multiclass / DART workloads (VERDICT r3 #4: bench the
# remaining reference workload families) ------------------------------

MC_CLASSES = 5
MC_TREES = int(os.environ.get("BENCH_MC_TREES", 50))


def make_extra_labels():
    """(continuous, 5-class) labels over make_data's x: the regression
    target is the same signal with fresh noise; classes are its
    quantile buckets (balanced)."""
    x, _ = make_data()
    rng = np.random.RandomState(SEED + 2)
    y_reg = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
             + 0.3 * rng.randn(N_ROWS)).astype(np.float32)
    edges = np.quantile(y_reg, np.linspace(0, 1, MC_CLASSES + 1)[1:-1])
    y_mc = np.digitize(y_reg, edges).astype(np.float32)
    return x, y_reg, y_mc


def _extra_train_file(tag, x, y):
    path = os.path.join(CACHE, "bench_%s_%d.train" % (tag, N_ROWS))
    if not os.path.exists(path):
        os.makedirs(CACHE, exist_ok=True)
        np.savetxt(path, np.concatenate([y[:, None], x], axis=1),
                   fmt="%.6g", delimiter="\t")
    return path


def _run_ours_workload(params, x, y, num_trees, field, warm_iters=1):
    import jax
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    # num_iterations sizes preallocated per-iteration state (the DART
    # device bank); the loop below drives the actual count
    cfg = Config.from_params({**params,
                              "num_iterations": str(num_trees)})
    ds = build_dataset(cfg, x, y)
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    # chunk-length warm-up (see run_ours): the segment tiling must
    # compile every executable it will use before the timed loop
    chunks = 4 if num_trees % 4 == 0 else 1
    per = num_trees // chunks
    warm = create_boosting(cfg, ds, obj)
    t0 = time.time()
    # a FULL chunk under batching: anything shorter can miss the
    # remainder-segment executable (e.g. K=8 tiling per=25 as 8,8,8,1 —
    # the K=1 compile would land inside the first timed chunk)
    _drive(warm, _warm_n(warm, per, warm_iters))
    jax.block_until_ready(warm.scores)
    compile_s = time.time() - t0
    del warm
    booster = create_boosting(cfg, ds, obj)
    # chunked min*chunks like the headline loop (see run_ours)
    chunk_s = []
    t_all = time.time()
    for _ in range(chunks):
        t0 = time.time()
        _drive(booster, per)
        jax.block_until_ready(booster.scores)
        float(np.asarray(booster.scores[0, 0]))
        chunk_s.append(time.time() - t0)
    # per-family warm-up wall (compile or persistent-cache load) —
    # VERDICT r4 weak #5 asks for compile cost visibility per family
    return {field: min(chunk_s) * chunks,
            field.replace("_train_s", "_wall_s"):
                round(time.time() - t_all, 3),
            field.replace("_train_s", "_compile_s"): round(compile_s, 3)}


def run_regression_pair(x, y_reg):
    ours = _run_ours_workload({**_params(), "objective": "regression"},
                              x, y_reg, NUM_TREES, "regression_train_s")
    ref = _run_reference_binary(
        ["objective=regression"],
        "refreg_%dx%d_t%d_l%d_b%d_cpu%d.json" % (
            N_ROWS, N_FEAT, NUM_TREES, NUM_LEAVES, MAX_BIN, os.cpu_count()),
        "ref_regression_train_s",
        train_file=_extra_train_file("reg", x, y_reg))
    return ours, ref


def run_multiclass_pair(x, y_mc):
    """num_class trees per iteration on both sides; ours runs the fused
    multiclass step (one dispatch per iteration, class-wise scan)."""
    ours = _run_ours_workload(
        {**_params(), "objective": "multiclass",
         "num_class": str(MC_CLASSES)},
        x, y_mc, MC_TREES, "multiclass_train_s")
    ref = _run_reference_binary(
        ["objective=multiclass", "num_class=%d" % MC_CLASSES],
        "refmc_%dx%d_k%d_t%d_l%d_b%d_cpu%d.json" % (
            N_ROWS, N_FEAT, MC_CLASSES, MC_TREES, NUM_LEAVES, MAX_BIN,
            os.cpu_count()),
        "ref_multiclass_train_s",
        train_file=_extra_train_file("mc", x, y_mc), num_trees=MC_TREES,
        metric="multi_logloss")
    return ours, ref


def run_dart_pair():
    x, y = make_data()
    # DART drops/re-adds trees every iteration on the host (dart.hpp's
    # score surgery), so it exercises the flush-every-iteration path
    ours = _run_ours_workload({**_params(), "objective": "binary",
                               "boosting_type": "dart"},
                              x, y, NUM_TREES, "dart_train_s")
    ref = _run_reference_binary(
        ["objective=binary", "boosting_type=dart"],
        "refdart_%dx%d_t%d_l%d_b%d_cpu%d.json" % (
            N_ROWS, N_FEAT, NUM_TREES, NUM_LEAVES, MAX_BIN, os.cpu_count()),
        "ref_dart_train_s")
    return ours, ref


# out-of-core ingest + chips-vs-throughput capture (ISSUE 10): synthetic
# Criteo-class files, sized small enough for CI and env-tunable for the
# honest at-scale run (BENCH_INGEST_MB=2048 for a 2 GB pass)
INGEST_MB = int(os.environ.get("BENCH_INGEST_MB", 48))
INGEST_TREES = int(os.environ.get("BENCH_INGEST_TREES", 6))
INGEST_ROWS = int(os.environ.get("BENCH_INGEST_ROWS", 60_000))
INGEST_MESHES = [int(s) for s in os.environ.get(
    "BENCH_INGEST_SHARDS", "1,2,4,8").split(",") if s.strip()]


def run_ingest_scale_bench():
    """Ingestion throughput (dense + LibSVM, rows/s and MB/s through
    the out-of-core shard writer) and the chips-vs-throughput table:
    shard-fed tree_learner=data training at 1/2/4/8 shards-of-mesh
    over the SAME manifest, with scaling efficiency vs the 1-shard
    run.  On a virtual-device CPU host the shards share physical
    cores, so efficiency there is a lower bound — the honest per-chip
    curve needs real multi-chip hardware (BASELINE.md flags the TPU
    recapture)."""
    import shutil

    import jax

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ingest.shards import load_sharded_dataset
    from lightgbm_tpu.ingest.synth import cached_file, generate
    from lightgbm_tpu.ingest.writer import ingest
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    out = {}
    # every config here shares the manifest fingerprint keys (max_bin
    # etc. at defaults) so training reuses the ingested shards as-is
    icfg = Config.from_params({"ingest_workers": "0",
                               "ingest_memory_budget_mb": "512",
                               # several shards per manifest: the
                               # training rounds must exercise the
                               # per-shard-window device feed
                               "ingest_shard_rows": "16384"})
    for fmt, key in (("tsv", "dense"), ("libsvm", "libsvm")):
        path = cached_file(CACHE, INGEST_MB << 20, fmt=fmt)
        sd = path + ".shards"
        shutil.rmtree(sd, ignore_errors=True)
        t0 = time.time()
        m = ingest([path], sd, icfg)
        wall = time.time() - t0
        size = os.path.getsize(path)
        out["ingest_%s_mb_s" % key] = round(size / (1 << 20) / wall, 2)
        out["ingest_%s_rows_s" % key] = round(m.num_rows / wall, 1)

    # chips-vs-throughput over one fixed-size training manifest
    train_src = os.path.join(CACHE, "ingest_scale_%d.tsv" % INGEST_ROWS)
    if not os.path.isfile(train_src):
        generate(train_src, rows=INGEST_ROWS, fmt="tsv", seed=7)
    scale_dir = train_src + ".shards"
    ingest([train_src], scale_dir, icfg)
    ndev = len(jax.devices())
    scale, eff = {}, {}
    base = None
    for k in INGEST_MESHES:
        if k > ndev:
            continue
        cfg = Config.from_params({
            "objective": "binary", "tree_learner": "data",
            "num_shards": str(k), "num_leaves": "15",
            "min_data_in_leaf": "20", "metric": "",
            "iter_batch": ITER_BATCH, "is_save_binary_file": "false"})
        ds = load_sharded_dataset(scale_dir, cfg)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        booster = create_boosting(cfg, ds, obj)
        _drive(booster, _warm_n(booster, 4, 2))
        booster._flush_pending()
        np.asarray(booster.scores).sum()
        t0 = time.time()
        _drive(booster, INGEST_TREES)
        booster._flush_pending()
        np.asarray(booster.scores).sum()
        steady = time.time() - t0
        rows_s = ds.num_data * INGEST_TREES / steady
        scale[str(k)] = round(rows_s, 1)
        if base is None:
            base = (k, rows_s)
        eff[str(k)] = round(rows_s / (base[1] * k / base[0]), 4)
        del booster, ds, obj
    out["ingest_scale_rows_s"] = scale
    out["ingest_scale_efficiency"] = eff
    out["ingest_scale_devices"] = ndev
    return out


# fused Pallas histogram+gain kernel A-B (BENCH_HIST_FUSED gate)
HIST_FUSED_ROWS = int(os.environ.get("BENCH_HIST_FUSED_ROWS", 0))
HIST_FUSED_REPS = int(os.environ.get("BENCH_HIST_FUSED_REPS", 0))


def run_hist_fused_bench():
    """A-B of the fused histogram+gain kernel vs the two-op oracle
    (leaf_histogram_masked + TWO find_best_split scan passes over the
    materialized [F, B, 3] tensors — the per-split work the fusion
    collapses), plus the shard-fed-vs-in-memory steady comparison with
    the prefetch overlap on.

    On an accelerator both sides run compiled at the bench shape; on a
    CPU container the kernels run in INTERPRET mode at a reduced shape
    — those numbers bound nothing about TPU (flagged in the output and
    in BASELINE.md), but the A-B structure and the byte-identity gates
    still machine-check."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.hist_pallas import (fold_leaf_mask,
                                              leaf_histogram_masked,
                                              leaf_histogram_masked_fused,
                                              make_gh2)
    from lightgbm_tpu.ops.split import (SplitParams, find_best_split,
                                        find_best_split_fused)

    on_accel = jax.default_backend() != "cpu"
    interpret = not on_accel
    rows = HIST_FUSED_ROWS or (1_048_576 if on_accel else 16_384)
    rows = -(-rows // 8192) * 8192
    reps = HIST_FUSED_REPS or (50 if on_accel else 3)
    feats, b = N_FEAT, 255
    rng = np.random.RandomState(SEED)
    bins = jnp.asarray(rng.randint(0, b, size=(feats, rows))
                       .astype(np.uint8))
    gh2 = make_gh2(jnp.asarray(rng.randn(rows).astype(np.float32)),
                   jnp.asarray((rng.rand(rows) + 0.1)
                               .astype(np.float32)))
    leaf_id = jnp.asarray(rng.randint(0, 4, size=rows).astype(np.int32))
    leaf_eff = fold_leaf_mask(leaf_id, jnp.ones(rows, bool))
    fmask = jnp.ones(feats, bool)
    params = SplitParams(MIN_DATA_IN_LEAF, 10.0, 0.0, 0.0, 0.0)
    parent_eff = fold_leaf_mask(jnp.zeros(rows, jnp.int32),
                                (leaf_id == 2) | (leaf_id == 3))
    parent = leaf_histogram_masked(bins, gh2, parent_eff, jnp.int32(0),
                                   max_bin=b, interpret=interpret)

    def stats(h):
        return (jnp.round(jnp.sum(h[0, :, 2])).astype(jnp.int32),
                jnp.sum(h[0, :, 0]), jnp.sum(h[0, :, 1]))

    small0 = leaf_histogram_masked(bins, gh2, leaf_eff, jnp.int32(2),
                                   max_bin=b, interpret=interpret)
    cs, sgs, shs = stats(small0)
    cl, sgl, shl = stats(parent - small0)

    def two_op():
        h = leaf_histogram_masked(bins, gh2, leaf_eff, jnp.int32(2),
                                  max_bin=b, interpret=interpret)
        s1 = find_best_split(h, cs, sgs, shs, fmask, params)
        s2 = find_best_split(parent - h, cl, sgl, shl, fmask, params)
        return s1, s2

    def fused():
        h, pfs, pfl = leaf_histogram_masked_fused(
            bins, gh2, leaf_eff, jnp.int32(2), parent, fmask,
            (cs, sgs, shs), (cl, sgl, shl), None, max_bin=b,
            params=params, interpret=interpret)
        s1 = find_best_split_fused(pfs, sgs, shs, params)
        s2 = find_best_split_fused(pfl, sgl, shl, params)
        return s1, s2

    def timed(fn):
        jax.block_until_ready(fn())   # warm/compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(fn())
            best = min(best, time.time() - t0)   # min-of-reps: noise-
        return best                              # robust on shared hosts

    off_s = timed(two_op)
    on_s = timed(fused)
    w_off, w_on = two_op(), fused()
    identical = all(
        bool(np.array_equal(np.asarray(getattr(a, f)),
                            np.asarray(getattr(bb, f))))
        for a, bb in zip(w_off, w_on) for f in a._fields)
    # the parity gate is a hard failure, not a JSON footnote — same
    # rule as the serving benches' byte-equality asserts
    assert identical, \
        "hist_fused A-B: fused BestSplit diverged from the two-op oracle"
    out = {
        "hist_fused_split_off_ms": round(off_s * 1e3, 3),
        "hist_fused_split_on_ms": round(on_s * 1e3, 3),
        "hist_fused_speedup": round(off_s / on_s, 4) if on_s else None,
        "hist_fused_bit_identical": identical,
        "hist_fused_rows": rows,
        "hist_fused_mode": "compiled" if on_accel else "interpret",
    }

    # shard-fed vs in-memory steady train, prefetch overlap ON; the
    # models must be byte-identical (the prefetcher changes timing only)
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ingest.shards import load_sharded_dataset
    from lightgbm_tpu.ingest.synth import generate
    from lightgbm_tpu.ingest.writer import ingest
    from lightgbm_tpu.io.dataset import load_dataset
    from lightgbm_tpu.models.gbdt import NO_LIMIT, create_boosting
    from lightgbm_tpu.objectives import create_objective

    src = os.path.join(CACHE, "hist_fused_feed_%d.tsv" % INGEST_ROWS)
    if not os.path.isfile(src):
        generate(src, rows=INGEST_ROWS, fmt="tsv", seed=11)
    shards = src + ".shards"
    # max_bin rides the manifest config fingerprint: ingest and train
    # must agree or the loader re-ingests (63 keeps the CPU-container
    # leg affordable — this leg compares LOAD paths and a steady RATIO,
    # not absolute tree cost)
    icfg = Config.from_params({"ingest_workers": "0",
                               "ingest_shard_rows": "16384",
                               "max_bin": "63",
                               "is_save_binary_file": "false"})
    ingest([src], shards, icfg)
    trees = INGEST_TREES
    steady, models = {}, {}
    for tag, data, prefetch in (("inmem", src, "0"),
                                ("shard", shards, "2")):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": "15", "max_bin": "63",
            "min_data_in_leaf": "20", "metric": "",
            "iter_batch": ITER_BATCH, "is_save_binary_file": "false",
            "ingest_prefetch": prefetch})
        t_load = time.time()
        ds = (load_sharded_dataset(data, cfg) if tag == "shard"
              else load_dataset(data, cfg))
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        booster = create_boosting(cfg, ds, obj)
        load_s = time.time() - t_load
        _drive(booster, _warm_n(booster, trees, 2))
        booster._flush_pending()
        np.asarray(booster.scores).sum()
        # chunked-min steady (the repo's convention): per-tree chunks,
        # min x trees — a shared-core container's transient stalls
        # otherwise dominate a ratio of two short loops
        chunk_s = []
        for _ in range(trees):
            t0 = time.time()
            _drive(booster, 1)
            booster._flush_pending()
            np.asarray(booster.scores).sum()
            chunk_s.append(time.time() - t0)
        steady[tag] = min(chunk_s) * trees
        out["%s_load_s" % tag] = round(load_s, 3)
        mp = os.path.join(CACHE, "hist_fused_%s.txt" % tag)
        booster.save_model_to_file(NO_LIMIT, True, mp)
        with open(mp) as f:
            models[tag] = f.read()
        del booster, ds, obj
    out["inmem_steady_s"] = round(steady["inmem"], 3)
    out["shard_fed_steady_s"] = round(steady["shard"], 3)
    out["shard_fed_vs_inmem_steady"] = round(
        steady["shard"] / steady["inmem"], 4)
    out["shard_fed_byte_identical"] = models["shard"] == models["inmem"]
    assert out["shard_fed_byte_identical"], \
        "shard-fed model diverged from the in-memory path with " \
        "prefetch on"
    return out


def main():
    # predict e2e measures FIRST, before this process touches JAX: a
    # chip belongs to one process, and the predict subprocess must not
    # find it held by its parent (ROADMAP S1).  Uses the model file from
    # the previous bench run when present; falls back to after-training.
    predict_extras = None
    model_path = os.path.join(CACHE, "bench_model.txt")
    if (os.environ.get("BENCH_PREDICT", "1") != "0"
            and os.path.exists(model_path)):
        try:
            predict_extras = run_predict_e2e(model_path)
        except Exception:
            # stale/corrupt model from an earlier run: leave None so the
            # post-training fallback retries with the fresh model
            predict_extras = None

    ours = run_ours()
    try:
        ref = run_reference()
    except Exception as e:  # reference unavailable: report ours alone
        ref = {"ref_train_s": None, "error": str(e)[:200]}
    ref_s = ref.get("ref_train_s") or 0.0

    extras = {}
    if os.environ.get("BENCH_RANK", "1") != "0":
        try:
            r = run_ours_rank()
            extras = {
                "rank_train_s": round(r["rank_train_s"], 3),
                "rank_wall_s": round(r["rank_wall_s"], 3),
            }
            # the tentpole's tree_learner=data rank line: the fused
            # query-sharded step vs the pre-fusion general per-tree
            # path on the SAME device mesh (the fused-vs-general
            # speedup recorded in BASELINE.md)
            rd = _run_rank_workload("rank_data",
                                    {"tree_learner": "data"})
            extras.update({
                "rank_data_train_s": round(rd["rank_data_train_s"], 3),
                "rank_data_wall_s": round(rd["rank_data_wall_s"], 3)})
            try:
                rg = _run_rank_workload(
                    "rank_data_general", {"tree_learner": "data"},
                    force_general=True)
                extras.update({
                    "rank_data_general_train_s": round(
                        rg["rank_data_general_train_s"], 3),
                    "rank_data_fused_vs_general": round(
                        rg["rank_data_general_train_s"]
                        / rd["rank_data_train_s"], 4)})
            except Exception as e:
                extras["rank_data_general_error"] = str(e)[:200]
            rr = run_reference_rank()
            extras.update({
                "ref_rank_train_s": rr["ref_rank_train_s"],
                "rank_vs_baseline": round(
                    rr["ref_rank_train_s"] / r["rank_train_s"], 4),
                "rank_data_vs_baseline": round(
                    rr["ref_rank_train_s"]
                    / rd["rank_data_train_s"], 4),
            })
        except Exception as e:
            extras["rank_error"] = str(e)[:200]

    if os.environ.get("BENCH_BAGGED", "1") != "0":
        try:
            bo = run_ours_bagged()
            extras.update({
                "bagged_train_s": round(bo["bagged_train_s"], 3),
                "bagged_steady_s": round(bo["bagged_steady_s"], 3),
                "bagged_wall_s": round(bo["bagged_wall_s"], 3),
                "bagged_compile_s": bo["bagged_compile_s"],
            })
            br = run_reference_bagged()
            extras.update({
                "ref_bagged_train_s": br["ref_bagged_train_s"],
                "bagged_vs_baseline": round(
                    br["ref_bagged_train_s"] / bo["bagged_train_s"], 4),
            })
        except Exception as e:
            extras["bagged_error"] = str(e)[:200]

    # the fraction sweep is independently gated: it builds its own data
    # and must keep machine-checking the scaling claim even when the
    # slower reference-vs-ours bagged comparison is skipped
    if os.environ.get("BENCH_BAG_SWEEP", "1") != "0":
        try:
            extras.update(run_bagged_sweep())
        except Exception as e:
            extras["bag_sweep_error"] = str(e)[:200]

    if os.environ.get("BENCH_FAMILIES", "1") != "0":
        # remaining reference workload families (VERDICT r3 #4):
        # regression, multiclass (fused K-trees-per-dispatch), DART —
        # each isolated so one family's failure keeps the others' numbers
        try:
            x_e, y_reg, y_mc = make_extra_labels()
        except Exception as e:
            x_e = None
            extras["families_error"] = str(e)[:200]
        if x_e is not None:
            try:
                ro, rr = run_regression_pair(x_e, y_reg)
                extras.update({
                    "regression_train_s": round(
                        ro["regression_train_s"], 3),
                    "regression_wall_s": ro.get("regression_wall_s"),
                    "regression_compile_s": ro.get("regression_compile_s"),
                    "ref_regression_train_s":
                        rr["ref_regression_train_s"],
                    "regression_vs_baseline": round(
                        rr["ref_regression_train_s"]
                        / ro["regression_train_s"], 4)})
            except Exception as e:
                extras["regression_error"] = str(e)[:200]
            try:
                mo, mr = run_multiclass_pair(x_e, y_mc)
                extras.update({
                    "multiclass_train_s": round(
                        mo["multiclass_train_s"], 3),
                    "multiclass_wall_s": mo.get("multiclass_wall_s"),
                    "multiclass_compile_s": mo.get("multiclass_compile_s"),
                    "ref_multiclass_train_s":
                        mr["ref_multiclass_train_s"],
                    "multiclass_vs_baseline": round(
                        mr["ref_multiclass_train_s"]
                        / mo["multiclass_train_s"], 4)})
            except Exception as e:
                extras["multiclass_error"] = str(e)[:200]
            del x_e, y_reg, y_mc
        try:
            do, dr = run_dart_pair()
            extras.update({
                "dart_train_s": round(do["dart_train_s"], 3),
                "dart_wall_s": do.get("dart_wall_s"),
                "dart_compile_s": do.get("dart_compile_s"),
                "ref_dart_train_s": dr["ref_dart_train_s"],
                "dart_vs_baseline": round(
                    dr["ref_dart_train_s"] / do["dart_train_s"], 4)})
        except Exception as e:
            extras["dart_error"] = str(e)[:200]

    if os.environ.get("BENCH_SERVE", "1") != "0":
        # online-serving family (serving/): closed-loop throughput +
        # p50/p99, micro-batching vs per-request dispatch — the
        # subsystem's headline is the batching speedup at identical
        # response bytes
        try:
            extras.update(run_serving_bench())
        except Exception as e:
            extras["serve_error"] = str(e)[:200]
        # worker-scaling sweep (serving/frontend.py): closed-loop
        # throughput at 1/4/8 workers + open-loop fixed-RPS p50/p99,
        # byte-equal responses required across every round
        try:
            extras.update(run_serving_scale_bench())
        except Exception as e:
            extras["serve_scale_error"] = str(e)[:200]
        # low-latency lane A-B (serving/flatforest.py + admission lane):
        # open-loop fixed-RPS single-row p50/p99, lane on vs off,
        # byte-equal responses required across the lanes
        try:
            extras.update(run_serving_lowlat_bench())
        except Exception as e:
            extras["serve_lowlat_error"] = str(e)[:200]
        # fleet scale-out sweep (serving/fleet.py): warm-hit throughput
        # vs single-model + cold-hit latency through the bounded pool
        try:
            extras.update(run_serving_fleet_bench())
        except Exception as e:
            extras["serve_fleet_error"] = str(e)[:200]

    if os.environ.get("BENCH_INGEST", "1") != "0":
        # out-of-core ingest throughput (dense + LibSVM) + the shard-fed
        # tree_learner=data chips-vs-throughput scaling table
        try:
            extras.update(run_ingest_scale_bench())
        except Exception as e:
            extras["ingest_error"] = str(e)[:200]

    if os.environ.get("BENCH_HIST_FUSED", "1") != "0":
        # fused histogram+gain kernel A-B (two-op oracle vs in-register
        # scan, bit-identity REQUIRED) + shard-fed-vs-in-memory steady
        # with the prefetch overlap on (byte-identity REQUIRED)
        try:
            extras.update(run_hist_fused_bench())
        except Exception as e:
            extras["hist_fused_error"] = str(e)[:200]

    if os.environ.get("BENCH_PREDICT", "1") != "0":
        if predict_extras is None:
            try:
                predict_extras = run_predict_e2e(ours["model_path"])
            except Exception as e:
                predict_extras = {"predict_error": str(e)[:200]}
        extras.update(predict_extras)

    # headline vs_baseline is the RAW wall-clock ratio (includes the
    # post-warm-up residual); the
    # steady-state extrapolation min(chunk)*4 is reported alongside as
    # vs_baseline_steady (ADVICE r1: wall is the honest primary).
    # SYMMETRIC reporting (VERDICT r5 item 5): every family emits BOTH
    # its chunked-steady `*_train_s` and raw `*_wall_s`; the map below
    # states which convention each vs_baseline ratio uses, so BASELINE
    # readers never have to guess.
    conventions = {"vs_baseline": "wall", "vs_baseline_steady": "steady"}
    for k in extras:
        if k.endswith("_vs_baseline") or k.endswith("_vs_general") \
                or k.endswith("_compact_speedup"):
            conventions[k] = "steady"
    if "predict_vs_baseline" in extras:
        # file-to-file predict has no chunked loop; both sides are
        # single-shot walls (ours best-of-2)
        conventions["predict_vs_baseline"] = "wall"
    if "serve_batch_speedup" in extras:
        # closed-loop client wall on both sides (batched vs batch-1)
        conventions["serve_batch_speedup"] = "wall"
    if "hist_fused_speedup" in extras:
        # best-of-reps kernel pair on one side, chunkless steady loops
        # on the other — both same-process same-shape A-Bs
        conventions["hist_fused_speedup"] = "steady"
        conventions["shard_fed_vs_inmem_steady"] = "steady"
    print(json.dumps({
        "metric": "train_100trees_1Mx28",
        "value": round(ours["train_total_s"], 3),
        "unit": "s",
        "vs_baseline": round(ref_s / ours["train_total_s"], 4),
        "ref_train_s": ref.get("ref_train_s"),
        "train_steady_s": round(ours["train_s"], 3),
        "vs_baseline_steady": round(ref_s / ours["train_s"], 4),
        "compile_s": round(ours["compile_s"], 3),
        "compile_cache": ours["compile_cache"],
        "compile_cache_hits": ours["compile_cache_hits"],
        "compile_cache_misses": ours["compile_cache_misses"],
        "iter_batch": ours["iter_batch"],
        "dispatches_per_tree": ours["dispatches_per_tree"],
        "device_gets_per_100_trees": ours["device_gets_per_100_trees"],
        "auc_holdout": round(ours["auc"], 5),
        "backend": ours["backend"],
        "ncpu": os.cpu_count(),
        "trees_per_s": round(NUM_TREES / ours["train_s"], 3),
        **extras,
        "vs_baseline_timing": conventions,
    }))


if __name__ == "__main__":
    sys.exit(main())
