"""Configuration system.

Re-implements the reference's key=value config surface (same keys, same
~60-entry alias table, same defaults and conflict checks) so the reference
`examples/*/train.conf` files run unchanged:
  - key list + defaults: reference include/LightGBM/config.h:89-245
  - alias table:         reference include/LightGBM/config.h:303-378
  - conflict checks:     reference src/io/config.cpp:129-177
  - CLI/config-file precedence (CLI wins, `#` comments):
                         reference src/application/application.cpp:46-104

TPU-specific additions (not in the reference) are grouped at the bottom of
Config; they control the JAX mesh instead of the socket/MPI bootstrap.
"""

from __future__ import annotations

__jax_free__ = True

import dataclasses
from typing import Dict, List, Optional, Tuple

from .utils import log

NO_LIMIT = -1

ALIAS_TABLE: Dict[str, str] = {
    "config": "config_file",
    "nthread": "num_threads",
    "num_thread": "num_threads",
    "boosting": "boosting_type",
    "boost": "boosting_type",
    "application": "objective",
    "app": "objective",
    "train_data": "data",
    "train": "data",
    "model_output": "output_model",
    "model_out": "output_model",
    "model_input": "input_model",
    "model_in": "input_model",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "valid": "valid_data",
    "test_data": "valid_data",
    "test": "valid_data",
    "is_sparse": "is_enable_sparse",
    "tranining_metric": "is_training_metric",
    "train_metric": "is_training_metric",
    "ndcg_at": "ndcg_eval_at",
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "num_leaf": "num_leaves",
    "sub_feature": "feature_fraction",
    "num_iteration": "num_iterations",
    "num_tree": "num_iterations",
    "num_round": "num_iterations",
    "num_trees": "num_iterations",
    "num_rounds": "num_iterations",
    "sub_row": "bagging_fraction",
    "shrinkage_rate": "learning_rate",
    "tree": "tree_learner",
    "topk": "top_k",
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "two_round_loading": "use_two_round_loading",
    "two_round": "use_two_round_loading",
    "mlist": "machine_list_file",
    "is_save_binary": "is_save_binary_file",
    "save_binary": "is_save_binary_file",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "verbosity": "verbose",
    "header": "has_header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "query": "group_column",
    "query_column": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "predict_raw_score": "is_predict_raw_score",
    "predict_leaf_index": "is_predict_leaf_index",
    "num_classes": "num_class",
}


def _parse_bool(v: str) -> bool:
    # reference ConfigBase::GetBool accepts false/-/0 as false, true/+/1 as true
    s = v.strip().lower()
    if s in ("false", "-", "0"):
        return False
    if s in ("true", "+", "1"):
        return True
    log.fatal("Parameter value should be \"true\"/\"+\"/\"1\" or \"false\"/\"-\"/\"0\", got \"%s\"" % v)


@dataclasses.dataclass
class Config:
    """All hyper-parameters, flattened (the reference nests them in
    OverallConfig{IO,Boosting{Tree},Objective,Metric,Network}Config; a flat
    dataclass is the idiomatic Python equivalent)."""

    # -- task / top-level ------------------------------------------------
    task: str = "train"                   # train | predict | serve |
    #                                       ingest | refresh
    num_threads: int = 0
    boosting_type: str = "gbdt"           # gbdt | dart
    objective: str = "regression"         # regression | binary | multiclass | lambdarank
    metric: List[str] = dataclasses.field(default_factory=list)
    tree_learner: str = "serial"          # serial | feature | data | voting
    top_k: int = 20                       # voting-parallel votes per shard
    is_parallel: bool = False
    is_parallel_find_bin: bool = False

    # -- IO --------------------------------------------------------------
    max_bin: int = 256
    num_class: int = 1
    data_random_seed: int = 1
    data: str = ""
    valid_data: List[str] = dataclasses.field(default_factory=list)
    output_model: str = "LightGBM_model.txt"
    output_result: str = "LightGBM_predict_result.txt"
    input_model: str = ""
    verbose: int = 1
    num_model_predict: int = NO_LIMIT
    is_pre_partition: bool = False
    is_enable_sparse: bool = True
    use_two_round_loading: bool = False
    is_save_binary_file: bool = False
    enable_load_from_binary_file: bool = True
    bin_construct_sample_cnt: int = 50000
    is_predict_leaf_index: bool = False
    is_predict_raw_score: bool = False
    has_header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""

    # -- objective -------------------------------------------------------
    sigmoid: float = 1.0
    label_gain: List[float] = dataclasses.field(default_factory=list)
    max_position: int = 20
    is_unbalance: bool = False

    # -- metric ----------------------------------------------------------
    ndcg_eval_at: List[int] = dataclasses.field(default_factory=lambda: [1, 2, 3, 4, 5])

    # -- tree ------------------------------------------------------------
    min_data_in_leaf: int = 100
    min_sum_hessian_in_leaf: float = 10.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    num_leaves: int = 127
    feature_fraction_seed: int = 2
    feature_fraction: float = 1.0
    histogram_pool_size: float = NO_LIMIT
    max_depth: int = NO_LIMIT

    # -- boosting --------------------------------------------------------
    metric_freq: int = 1                  # reference BoostingConfig::output_freq
    is_training_metric: bool = False
    num_iterations: int = 10
    learning_rate: float = 0.1
    bagging_fraction: float = 1.0
    bagging_seed: int = 3
    bagging_freq: int = 0
    early_stopping_round: int = 0
    drop_rate: float = 0.01
    drop_seed: int = 4

    # -- network (reference socket/MPI keys, accepted for config-file
    #    compatibility; the JAX process bootstrap replaces their function) --
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_file: str = ""

    # -- TPU-native additions --------------------------------------------
    num_shards: int = 0                   # 0 = all visible devices when tree_learner=data
    hist_dtype: str = "float32"           # histogram accumulator dtype
    hist_impl: str = "auto"               # auto | xla | pallas
    hist_agg: str = "psum"                # psum | scatter (tree_learner=data)
    rank_impl: str = "device"             # device | native (lambdarank gradients)
    hist_ordered: str = "auto"            # auto | off: ordered-partition mode —
    #                                       block-list histogram sweeps + rows
    #                                       re-sorted by the previous tree's
    #                                       leaves every hist_reorder_every
    #                                       trees (serial pallas learner)
    hist_reorder_every: int = 16          # trees between row re-sorts
    bag_compact: str = "auto"             # auto | on | off: bag-compacted fused
    #                                       training — in-bag rows arranged into
    #                                       a contiguous static window at every
    #                                       re-bagging so histogram/grow work
    #                                       scales with bagging_fraction; auto
    #                                       engages when bagging is on,
    #                                       bagging_fraction <= 0.8 and
    #                                       hist_dtype=float32 (the f64 parity
    #                                       configuration keeps the masked
    #                                       full-sweep oracle)
    iter_batch: str = "auto"              # auto | N | 1: boosting iterations
    #                                       scanned per device dispatch
    #                                       (models/gbdt.py train_segment).
    #                                       Segments end at every metric /
    #                                       early-stop / re-bagging / re-sort
    #                                       boundary, so observable behavior
    #                                       is unchanged and K>1 is bit-parity
    #                                       with the per-iteration oracle
    #                                       (iter_batch=1); auto picks a K
    #                                       that divides metric_freq on
    #                                       accelerators and 1 on CPU (the
    #                                       K-scan saves one host dispatch
    #                                       and sync per iteration, which
    #                                       the CPU backend's extra compile
    #                                       time does not repay)
    donate_buffers: bool = True
    device_type: str = ""                 # "" = default JAX platform | cpu | tpu

    # -- online serving (task=serve; serving/) ---------------------------
    serve_host: str = "127.0.0.1"
    serve_port: int = 8080                # 0 = pick a free port
    serve_max_batch_rows: int = 8192      # rows per coalesced dispatch
    serve_batch_timeout_ms: float = 2.0   # micro-batching window
    serve_backend: str = "auto"           # auto | jax | native
    serve_max_inflight_rows: int = 65536  # admission control: rows in
    #                                       flight before new requests
    #                                       get a fast 503 + Retry-After
    #                                       instead of unbounded queueing
    serve_breaker_threshold: int = 3      # consecutive device-dispatch
    #                                       failures before the circuit
    #                                       breaker pins serving to the
    #                                       JAX-free native predictor
    serve_retry_after_s: float = 1.0      # Retry-After on overload 503s
    serve_workers: int = 1                # SO_REUSEPORT worker processes
    #                                       (serving/frontend.py): N
    #                                       processes share one listen
    #                                       port, each with its own warm
    #                                       forest; 1 = the in-process
    #                                       single server
    serve_matmul: str = "auto"            # auto | on | off: route serve
    #                                       batches >= serve_matmul_min_rows
    #                                       through the device matmul
    #                                       predictor (ops/predict.
    #                                       predict_leaf_matmul) instead
    #                                       of the stacked descent; auto
    #                                       engages on accelerators only
    #                                       (CPU descent wins there), on
    #                                       forces (tests/CPU parity)
    serve_matmul_min_rows: int = 1024     # row threshold for the matmul
    #                                       route (below it the descent
    #                                       dispatch is cheaper)
    serve_models: str = ""                # comma-separated extra model
    #                                       paths registered in the
    #                                       multi-model fleet at startup
    #                                       (serving/fleet.py); reachable
    #                                       via /predict?model=<path>
    serve_fleet_max_models: int = 64      # warm-pool capacity: at most
    #                                       this many forests stay warm
    #                                       (LRU + age eviction below);
    #                                       registered models past it
    #                                       re-warm on demand.  Cold
    #                                       fleet loads warm LAZILY
    #                                       (flat table + host packs
    #                                       only), so the pool scales
    #                                       toward thousands of
    #                                       per-tenant models
    serve_fleet_evict_age_s: float = 0.0  # age-based fleet eviction:
    #                                       warm non-default models idle
    #                                       longer than this drop from
    #                                       the pool (they stay
    #                                       registered and re-warm on
    #                                       the next hit); 0 = LRU
    #                                       capacity only
    serve_low_latency: str = "auto"       # auto | on | off: the
    #                                       latency-class admission lane
    #                                       — requests of at most
    #                                       serve_low_latency_max_rows
    #                                       rows skip the micro-batcher's
    #                                       coalescing window and
    #                                       dispatch synchronously on
    #                                       the jax-free flat-table
    #                                       engine (serving/flatforest).
    #                                       auto clamps the row bound
    #                                       below serve_matmul_min_rows;
    #                                       on fatals on that
    #                                       contradiction instead
    serve_low_latency_max_rows: int = 16  # largest request (rows) the
    #                                       fast lane admits; bigger
    #                                       requests ride the coalesced
    #                                       batch path

    # -- out-of-core ingestion (ingest/) ---------------------------------
    ingest_dir: str = ""                  # task=ingest output directory
    #                                       ("" = <data>.shards); training
    #                                       accepts data=<ingest_dir>
    ingest_memory_budget_mb: int = 1024   # hard host-memory budget for
    #                                       the chunked text->shard bin
    #                                       pass (bounds chunk size,
    #                                       in-flight worker results and
    #                                       the shard assembly buffer)
    ingest_shard_rows: int = 0            # rows per shard file (0 = auto
    #                                       from the memory budget)
    ingest_workers: int = 0               # parallel parse worker
    #                                       processes (0 = auto, 1 =
    #                                       inline single-process)
    ingest_prefetch: int = 2              # shard windows staged ahead by
    #                                       the background prefetch
    #                                       thread when training feeds
    #                                       from an ingest directory:
    #                                       the NEXT window pages in from
    #                                       disk while the previous
    #                                       device_put's transfer is in
    #                                       flight (bounded queue; host
    #                                       memory holds at most
    #                                       2 + ingest_prefetch windows —
    #                                       queued + producer-staged +
    #                                       consumer-held).
    #                                       0 = synchronous (the oracle:
    #                                       byte-identical models either
    #                                       way)

    # -- continuous refresh (task=refresh; refresh/agent.py) -------------
    refresh_drop_dir: str = ""            # watched drop directory: new
    #                                       training text files landing
    #                                       here trigger retrain cycles
    refresh_work_dir: str = ""            # agent scratch/state dir
    #                                       ("" = <drop_dir>/.refresh)
    refresh_serve_url: str = ""           # base URL of the serving
    #                                       fleet the agent deploys to
    #                                       (e.g. http://127.0.0.1:8080)
    refresh_eval_data: str = ""           # held-out labeled rows
    #                                       (task=predict data format)
    #                                       mirrored to champion AND
    #                                       challenger for shadow eval
    refresh_period_s: float = 30.0        # min seconds between cycles
    refresh_poll_s: float = 0.5           # drop-dir scan cadence; a
    #                                       file is offered only once
    #                                       its (size, mtime) held
    #                                       still across two scans
    refresh_rounds: int = 0               # boosting rounds per retrain
    #                                       (0 = num_iterations)
    refresh_min_gain: float = 0.0         # challenger must beat the
    #                                       champion's shadow-eval loss
    #                                       by more than this to be
    #                                       promoted (ties reject)
    refresh_deadline_s: float = 120.0     # per-step overall deadline
    #                                       (train / push / eval /
    #                                       promote each retry with
    #                                       backoff under it)
    refresh_breaker_threshold: int = 3    # consecutive failed cycles
    #                                       before the agent's circuit
    #                                       breaker opens (champion
    #                                       keeps serving)
    refresh_cooldown_s: float = 30.0      # how long an open breaker
    #                                       skips cycles before the
    #                                       next (half-open) attempt
    refresh_max_cycles: int = 0           # exit after N completed
    #                                       cycle attempts (0 = run
    #                                       until SIGTERM — production;
    #                                       N is for smokes/tests)
    refresh_train_args: str = ""          # extra space-separated
    #                                       key=value args forwarded to
    #                                       the retrain subprocess
    refresh_ingest: bool = False          # route each cycle's drop
    #                                       data through task=ingest
    #                                       and retrain from the shard
    #                                       directory (out-of-core
    #                                       lane) instead of the text
    #                                       file directly
    refresh_status_port: int = 0          # agent /metrics + /healthz
    #                                       port (0 = pick a free port,
    #                                       -1 = disabled)

    # -- fault tolerance (resilience/) -----------------------------------
    snapshot_period: int = 0              # snapshot every N iterations
    #                                       (0 = off); requires
    #                                       snapshot_dir
    snapshot_dir: str = ""                # where snapshots live
    snapshot_keep: int = 4                # newest snapshots retained per
    #                                       rank (0 = keep everything)
    resume: str = "off"                   # off | auto | <snapshot path>:
    #                                       auto picks the latest VALID
    #                                       snapshot in snapshot_dir,
    #                                       skipping corrupt ones
    faults: str = ""                      # fault-injection schedule
    #                                       (resilience/faults.py; also
    #                                       env LGBM_TPU_FAULTS)
    dist_connect_deadline_s: float = 120.0  # overall deadline for the
    #                                         distributed-runtime connect
    #                                         retry loop
    dist_timeout_s: float = 600.0         # per-collective deadline; a
    #                                       dead peer raises NetworkError
    #                                       instead of hanging (0 = wait
    #                                       forever)

    # ---------------------------------------------------------------------
    @staticmethod
    def from_params(params: Dict[str, str]) -> "Config":
        params = apply_aliases(params)
        c = Config()
        getp = params.get

        def set_int(key: str, attr: Optional[str] = None) -> None:
            if key in params:
                setattr(c, attr or key, int(params[key]))

        def set_float(key: str, attr: Optional[str] = None) -> None:
            if key in params:
                setattr(c, attr or key, float(params[key]))

        def set_bool(key: str, attr: Optional[str] = None) -> None:
            if key in params:
                setattr(c, attr or key, _parse_bool(params[key]))

        def set_str(key: str, attr: Optional[str] = None) -> None:
            if key in params:
                setattr(c, attr or key, params[key].strip())

        # top-level
        set_int("num_threads")
        if "task" in params:
            t = getp("task").lower()
            if t in ("train", "training"):
                c.task = "train"
            elif t in ("predict", "prediction", "test"):
                c.task = "predict"
            elif t in ("serve", "serving"):
                c.task = "serve"
            elif t in ("ingest", "ingestion"):
                c.task = "ingest"
            elif t == "refresh":
                c.task = "refresh"
            else:
                log.fatal("Unknown task type %s" % t)
        if "boosting_type" in params:
            b = getp("boosting_type").lower()
            if b in ("gbdt", "gbrt"):
                c.boosting_type = "gbdt"
            elif b == "dart":
                c.boosting_type = "dart"
            else:
                log.fatal("Unknown boosting type %s" % b)
        if "objective" in params:
            c.objective = getp("objective").lower()
        if "metric" in params:
            seen = []
            for m in getp("metric").lower().split(","):
                m = m.strip()
                if m and m not in seen:
                    seen.append(m)
            c.metric = seen
        if "tree_learner" in params:
            tl = getp("tree_learner").lower()
            if tl in ("serial", "feature", "data", "voting"):
                c.tree_learner = tl
            elif tl in ("feature_parallel",):
                c.tree_learner = "feature"
            elif tl in ("data_parallel",):
                c.tree_learner = "data"
            elif tl in ("voting_parallel",):
                c.tree_learner = "voting"
            else:
                log.fatal("Unknown tree learner type %s" % tl)

        # IO
        set_int("max_bin")
        set_int("data_random_seed")
        set_str("data")
        if "valid_data" in params:
            c.valid_data = [s.strip() for s in getp("valid_data").split(",") if s.strip()]
        set_str("output_model")
        set_str("output_result")
        set_str("input_model")
        set_int("verbose")
        set_int("num_model_predict")
        set_bool("is_pre_partition")
        set_bool("is_enable_sparse")
        set_bool("use_two_round_loading")
        set_bool("is_save_binary_file")
        set_bool("enable_load_from_binary_file")
        set_int("bin_construct_sample_cnt")
        set_bool("is_predict_leaf_index")
        set_bool("is_predict_raw_score")
        set_bool("has_header")
        set_str("label_column")
        set_str("weight_column")
        set_str("group_column")
        set_str("ignore_column")

        # objective / metric
        set_float("sigmoid")
        if "label_gain" in params:
            c.label_gain = [float(x) for x in getp("label_gain").split(",") if x.strip()]
        set_int("max_position")
        set_bool("is_unbalance")
        set_int("num_class")
        if "ndcg_eval_at" in params:
            c.ndcg_eval_at = [int(x) for x in getp("ndcg_eval_at").split(",") if x.strip()]

        # tree
        set_int("min_data_in_leaf")
        set_float("min_sum_hessian_in_leaf")
        set_float("lambda_l1")
        set_float("lambda_l2")
        set_float("min_gain_to_split")
        set_int("num_leaves")
        set_int("feature_fraction_seed")
        set_float("feature_fraction")
        set_float("histogram_pool_size")
        set_int("max_depth")

        # boosting
        set_int("metric_freq")
        set_bool("is_training_metric")
        set_int("num_iterations")
        set_float("learning_rate")
        set_float("bagging_fraction")
        set_int("bagging_seed")
        set_int("bagging_freq")
        set_int("early_stopping_round")
        set_float("drop_rate")
        set_int("drop_seed")

        # network
        set_int("num_machines")
        set_int("local_listen_port")
        set_int("time_out")
        set_str("machine_list_file")

        # tpu
        set_int("num_shards")
        set_int("top_k")
        set_str("hist_dtype")
        set_str("hist_impl")
        set_str("hist_agg")
        set_str("rank_impl")
        set_str("hist_ordered")
        set_int("hist_reorder_every")
        set_str("bag_compact")
        set_str("iter_batch")
        set_bool("donate_buffers")
        set_str("device_type")
        set_str("serve_host")
        set_int("serve_port")
        set_int("serve_max_batch_rows")
        set_float("serve_batch_timeout_ms")
        set_str("serve_backend")
        set_int("serve_max_inflight_rows")
        set_int("serve_breaker_threshold")
        set_float("serve_retry_after_s")
        set_int("serve_workers")
        set_str("serve_matmul")
        set_int("serve_matmul_min_rows")
        set_str("serve_models")
        set_int("serve_fleet_max_models")
        set_float("serve_fleet_evict_age_s")
        set_str("serve_low_latency")
        set_int("serve_low_latency_max_rows")
        set_str("ingest_dir")
        set_int("ingest_memory_budget_mb")
        set_int("ingest_shard_rows")
        set_int("ingest_workers")
        set_int("ingest_prefetch")
        set_str("refresh_drop_dir")
        set_str("refresh_work_dir")
        set_str("refresh_serve_url")
        set_str("refresh_eval_data")
        set_float("refresh_period_s")
        set_float("refresh_poll_s")
        set_int("refresh_rounds")
        set_float("refresh_min_gain")
        set_float("refresh_deadline_s")
        set_int("refresh_breaker_threshold")
        set_float("refresh_cooldown_s")
        set_int("refresh_max_cycles")
        set_str("refresh_train_args")
        set_bool("refresh_ingest")
        set_int("refresh_status_port")
        set_int("snapshot_period")
        set_str("snapshot_dir")
        set_int("snapshot_keep")
        set_str("resume")
        set_str("faults")
        set_float("dist_connect_deadline_s")
        set_float("dist_timeout_s")
        if c.serve_backend not in ("auto", "jax", "native"):
            log.fatal("Unknown serve_backend %s (expect auto|jax|native)"
                      % c.serve_backend)
        if c.serve_max_batch_rows < 1:
            log.fatal("serve_max_batch_rows must be >= 1")
        if c.serve_batch_timeout_ms < 0:
            log.fatal("serve_batch_timeout_ms must be >= 0")
        if c.serve_max_inflight_rows < 1:
            log.fatal("serve_max_inflight_rows must be >= 1")
        if c.serve_breaker_threshold < 1:
            log.fatal("serve_breaker_threshold must be >= 1")
        if c.serve_retry_after_s < 0:
            log.fatal("serve_retry_after_s must be >= 0")
        if c.serve_workers < 1:
            log.fatal("serve_workers must be >= 1")
        if c.serve_matmul not in ("auto", "on", "off"):
            log.fatal("Unknown serve_matmul %s (expect auto|on|off)"
                      % c.serve_matmul)
        if c.serve_matmul_min_rows < 1:
            log.fatal("serve_matmul_min_rows must be >= 1")
        if c.serve_fleet_max_models < 1:
            log.fatal("serve_fleet_max_models must be >= 1")
        if c.serve_fleet_evict_age_s < 0:
            log.fatal("serve_fleet_evict_age_s must be >= 0")
        if c.serve_low_latency not in ("auto", "on", "off"):
            log.fatal("Unknown serve_low_latency %s (expect auto|on|off)"
                      % c.serve_low_latency)
        if c.serve_low_latency_max_rows < 1:
            log.fatal("serve_low_latency_max_rows must be >= 1")
        if c.serve_low_latency == "on" \
                and c.serve_low_latency_max_rows \
                >= c.serve_matmul_min_rows:
            # contradictory routing: the forced-on fast lane would eat
            # batches the matmul route is configured to serve.  auto
            # resolves this by clamping the lane bound below the
            # threshold; forcing both is a config error, not a silent
            # precedence pick
            log.fatal("serve_low_latency_max_rows (%d) must be below "
                      "serve_matmul_min_rows (%d) with "
                      "serve_low_latency=on; lower the lane bound or "
                      "use serve_low_latency=auto (it clamps)"
                      % (c.serve_low_latency_max_rows,
                         c.serve_matmul_min_rows))
        if c.ingest_memory_budget_mb < 8:
            log.fatal("ingest_memory_budget_mb must be >= 8")
        if c.ingest_shard_rows < 0:
            log.fatal("ingest_shard_rows must be >= 0 (0 = auto)")
        if c.ingest_workers < 0:
            log.fatal("ingest_workers must be >= 0 (0 = auto)")
        if c.refresh_period_s < 0:
            log.fatal("refresh_period_s must be >= 0")
        if c.refresh_poll_s <= 0:
            log.fatal("refresh_poll_s must be > 0")
        if c.refresh_rounds < 0:
            log.fatal("refresh_rounds must be >= 0 (0 = num_iterations)")
        if c.refresh_min_gain < 0:
            # a negative tolerance would promote a challenger whose
            # shadow loss is strictly WORSE — violating the invariant
            # that a losing challenger is never made default
            log.fatal("refresh_min_gain must be >= 0")
        if c.refresh_deadline_s <= 0:
            log.fatal("refresh_deadline_s must be > 0")
        if c.refresh_breaker_threshold < 1:
            log.fatal("refresh_breaker_threshold must be >= 1")
        if c.refresh_cooldown_s < 0:
            log.fatal("refresh_cooldown_s must be >= 0")
        if c.refresh_max_cycles < 0:
            log.fatal("refresh_max_cycles must be >= 0 (0 = forever)")
        if c.refresh_status_port < -1:
            log.fatal("refresh_status_port must be >= -1 "
                      "(-1 = disabled, 0 = pick a free port)")
        if c.task == "refresh":
            if not c.refresh_drop_dir:
                log.fatal("task=refresh requires refresh_drop_dir")
            if not c.refresh_serve_url:
                log.fatal("task=refresh requires refresh_serve_url")
            if not c.refresh_eval_data:
                log.fatal("task=refresh requires refresh_eval_data "
                          "(held-out rows for shadow eval)")
            if not c.input_model:
                log.fatal("task=refresh requires input_model (the "
                          "starting champion)")
        if c.snapshot_period < 0:
            log.fatal("snapshot_period must be >= 0")
        if c.snapshot_keep < 0:
            log.fatal("snapshot_keep must be >= 0")
        if c.snapshot_period > 0 and not c.snapshot_dir:
            log.fatal("snapshot_period requires snapshot_dir")
        if c.resume == "auto" and not c.snapshot_dir:
            log.fatal("resume=auto requires snapshot_dir")
        if c.device_type not in ("", "cpu", "tpu"):
            log.fatal("Unknown device_type %s (expect cpu|tpu)"
                      % c.device_type)
        if c.device_type == "tpu" and c.serve_backend == "native" \
                and c.task == "serve":
            # device_type=tpu never ends on the host engine with exit 0
            log.fatal("device_type=tpu contradicts serve_backend=native "
                      "(the jax-free host engine)")
        if c.hist_impl not in ("auto", "xla", "pallas"):
            log.fatal("Unknown hist_impl %s (expect auto|xla|pallas)"
                      % c.hist_impl)
        if c.hist_agg not in ("psum", "scatter"):
            log.fatal("Unknown hist_agg %s (expect psum|scatter)"
                      % c.hist_agg)
        if c.rank_impl not in ("device", "native"):
            log.fatal("Unknown rank_impl %s (expect device|native)"
                      % c.rank_impl)
        if c.hist_ordered not in ("auto", "off"):
            log.fatal("Unknown hist_ordered %s (expect auto|off)"
                      % c.hist_ordered)
        if c.ingest_prefetch < 0:
            log.fatal("ingest_prefetch must be >= 0 (0 = synchronous)")
        if c.bag_compact not in ("auto", "on", "off"):
            log.fatal("Unknown bag_compact %s (expect auto|on|off)"
                      % c.bag_compact)
        if c.iter_batch != "auto":
            try:
                ib = int(c.iter_batch)
            except ValueError:
                ib = 0
            if ib < 1:
                log.fatal("iter_batch must be 'auto' or an integer >= 1 "
                          "(got %s)" % c.iter_batch)
        if c.hist_dtype not in ("float32", "float64"):
            log.fatal("Unknown hist_dtype %s (expect float32|float64)"
                      % c.hist_dtype)

        c.check_param_conflict()
        log.set_level_from_verbosity(c.verbose)
        return c

    def check_param_conflict(self) -> None:
        # mirrors reference src/io/config.cpp:129-177
        multiclass = self.objective == "multiclass"
        if multiclass:
            if self.num_class <= 1:
                log.fatal("Number of classes should be specified and greater than 1 for multiclass training")
        else:
            if self.task == "train" and self.num_class != 1:
                log.fatal("Number of classes must be 1 for non-multiclass training")
        for m in self.metric:
            m_multi = m in ("multi_logloss", "multi_error")
            if (multiclass and not m_multi) or (not multiclass and m_multi):
                log.fatal("Objective and metrics don't match")
        # In the reference, num_machines>1 selects distributed training; on
        # TPU a "machine" is a mesh shard, so num_machines>1 with serial
        # learner collapses to serial (exactly as the reference does).
        if self.num_machines > 1:
            self.is_parallel = True
        else:
            self.is_parallel = False
        if self.tree_learner == "serial":
            self.is_parallel = False
            self.num_machines = 1
            self.is_parallel_find_bin = False
        elif self.tree_learner == "feature":
            self.is_parallel_find_bin = False
        elif self.tree_learner == "data":
            self.is_parallel = True
            self.is_parallel_find_bin = True
            if self.histogram_pool_size >= 0:
                log.warning(
                    "Histogram LRU queue was enabled (histogram_pool_size=%f). "
                    "Will disable this to reduce communication costs" % self.histogram_pool_size)
                self.histogram_pool_size = NO_LIMIT
        elif self.tree_learner == "voting":
            self.is_parallel = True
            self.is_parallel_find_bin = True
            if self.top_k <= 0:
                log.fatal("top_k must be positive for voting-parallel")


def apply_aliases(params: Dict[str, str]) -> Dict[str, str]:
    out = dict(params)
    for k, v in params.items():
        canonical = ALIAS_TABLE.get(k)
        if canonical is not None and canonical not in out:
            out[canonical] = v
    return out


def parse_kv_line(line: str) -> Optional[Tuple[str, str]]:
    line = line.split("#", 1)[0].strip()
    if not line:
        return None
    parts = line.split("=", 1)
    if len(parts) != 2:
        return None
    key = parts[0].strip().strip('"').strip("'")
    val = parts[1].strip().strip('"').strip("'")
    if not key:
        return None
    return key, val


def load_parameters(argv: List[str]) -> Dict[str, str]:
    """CLI args + optional config file; CLI wins.
    Mirrors Application::LoadParameters (reference src/application/application.cpp:46-104)."""
    cli: Dict[str, str] = {}
    for arg in argv:
        kv = parse_kv_line(arg)
        if kv is None:
            log.warning("Unknown parameter %s" % arg)
            continue
        cli[kv[0]] = kv[1]
    params: Dict[str, str] = {}
    config_file = cli.get("config") or cli.get("config_file")
    if config_file:
        with open(config_file, "r") as f:
            for line in f:
                kv = parse_kv_line(line)
                if kv is not None:
                    params.setdefault(kv[0], kv[1])
    # CLI priority
    params.update(cli)
    return params
