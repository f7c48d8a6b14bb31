"""Application layer: `python -m lightgbm_tpu key=value ...`.

Mirrors the reference CLI (src/application/application.cpp, src/main.cpp):
same key=value arguments, config-file handling, train/predict tasks, and
iteration logging, so the reference examples' train.conf/predict.conf run
unchanged.  The Network::Init socket bootstrap is replaced by the JAX mesh
(parallel/), selected by tree_learner=data.
"""

from __future__ import annotations

__jax_free__ = True

import os
import signal
import sys
import time
from typing import Any, List, Optional, TYPE_CHECKING

import numpy as np

from . import config as config_mod
from .config import Config
from .utils import log, spans

if TYPE_CHECKING:  # annotation-only names; runtime imports stay lazy
    from .io.dataset import Dataset
    from .metrics import Metric
    from .models.gbdt import GBDT

# Heavy modules (io.dataset, models.gbdt, metrics, objectives — all of
# which pull in jax) import lazily inside the train / fallback-predict
# paths: task=predict normally runs entirely through the native
# predict_fast module, where the JAX import+backend cost would be a
# multi-second tax the reference binary doesn't pay.


class Application:
    def __init__(self, argv: List[str]):
        params = config_mod.load_parameters(argv)
        self.config = Config.from_params(params)
        if self.config.faults:
            # deterministic fault injection (chaos testing): config key
            # wins over the LGBM_TPU_FAULTS environment schedule
            from .resilience.faults import configure
            configure(self.config.faults)

    def _apply_device_type(self) -> None:
        # pins device_type, initializes the backend and is fatal when
        # it is not the one asked for (utils/device.py)
        from .utils.device import resolve_device
        resolve_device(self.config.device_type)

    def run(self) -> None:
        if self.config.task == "train":
            self.init_train()
            self.train()
        elif self.config.task == "ingest":
            # out-of-core text -> binned shard directory (ingest/):
            # host-only preprocessing, deliberately jax-free — TB-scale
            # ingest lanes must not pay a backend init
            from .ingest.writer import run_ingest_cli
            run_ingest_cli(self.config)
        elif self.config.task == "refresh":
            # continuous train->deploy agent (refresh/agent.py):
            # jax-free supervisor lane like the serve front-end — it
            # only watches, spawns retrain subprocesses and talks HTTP
            from .refresh.agent import run_refresh_cli
            run_refresh_cli(self.config)
        elif self.config.task == "serve":
            # warm-model HTTP prediction service (serving/): jax imports
            # lazily inside the forest only when its engine is selected,
            # so serve_backend=native keeps the jax-free startup
            # profile — including the low-latency lane, whose flat-table
            # engine (serving/flatforest.py) is jax-free by contract
            log.info("serve: low-latency lane %s (serve_low_latency_"
                     "max_rows=%d)" % (self.config.serve_low_latency,
                                       self.config.serve_low_latency_max_rows))
            if self.config.serve_workers > 1:
                # multi-process front-end: the SUPERVISOR stays jax-free
                # (it only forks and watches); each spawned worker
                # resolves its device itself
                from .serving.frontend import frontend_forever
                frontend_forever(self.config)
                return
            # the device is resolved where the forest picks its engine
            # (ServingForest._pick_engine -> utils/device.py)
            from .serving.server import serve_forever
            serve_forever(self.config)
        else:
            if not os.environ.get("LGBM_TPU_NO_FAST_PREDICT"):
                from .predict_fast import try_fast_predict
                if try_fast_predict(self.config):
                    return
            self._apply_device_type()
            self.init_predict()
            self.predict()

    # ------------------------------------------------------------------
    def init_train(self) -> None:
        cfg = self.config
        from .utils.device import pin_platform
        # pin only, before anything touches a JAX backend:
        # jax.distributed.initialize refuses once a backend is live,
        # and init_distributed reads jax_platforms to choose the CPU
        # collectives.  The backend check (_apply_device_type) comes
        # after the distributed runtime is up
        pin_platform(cfg.device_type)
        # multi-host: bring up the JAX distributed runtime from the
        # machine list (replaces Network::Init, application.cpp:185).
        # Each process loads its row shard (query-granular for ranking;
        # valid files shard the same way), device placement goes through
        # make_array_from_process_local_data (parallel/mesh.py
        # _put_sharded), metrics allreduce partial sums so every rank
        # reports GLOBAL values, and the early-stop decision is
        # OR-allreduced across ranks.  Seeds/feature_fraction sync by
        # min and a config fingerprint check rejects inconsistent
        # per-rank hyper-parameters (GlobalSyncUpByMin,
        # application.cpp:119,188-193,255-282).
        from .io.dataset import load_dataset
        from .metrics import create_metrics
        from .models.gbdt import GBDT, create_boosting
        from .objectives import create_objective

        self.rank, self.num_machines = 0, 1
        if cfg.num_machines > 1:
            from .parallel.dist import (check_config_fingerprint,
                                        init_distributed, sync_config_by_min)
            self.rank, self.num_machines = init_distributed(cfg)
            sync_config_by_min(cfg)
            check_config_fingerprint(cfg)
        self._apply_device_type()
        self.boosting_old: Optional[GBDT] = None
        self._warm_start_ckpt: Optional[str] = None
        if cfg.input_model:
            from .resilience.snapshot import is_checkpoint_file
            if is_checkpoint_file(cfg.input_model):
                # a CHECKPOINT archive: bit-exact warm start via the
                # resume mechanism (loaded below, once the booster has
                # its datasets) — continues to num_iterations TOTAL
                self._warm_start_ckpt = cfg.input_model
            else:
                # model TEXT: continued training (application.cpp:
                # 106-180) — predict init scores with the old model,
                # then grow num_iterations NEW trees on top
                self.boosting_old = GBDT(cfg, None, None)
                with open(cfg.input_model) as f:
                    self.boosting_old.load_model_from_string(f.read())

        self.objective = create_objective(cfg)
        # load and binning of every file: the record's seconds are the
        # log line's
        loading = spans.startup(spans.STARTUP_DATASET)
        with loading as loaded:
            # feature-parallel premise (reference
            # feature_parallel_tree_learner.cpp:45-78): every machine holds
            # ALL rows — only the bin matrix splits, along features.  Rows
            # then need no sharding, and metrics are already global on every
            # rank (a cross-rank sum would double-count).
            feat_parallel = cfg.tree_learner == "feature"
            row_rank = 0 if feat_parallel else self.rank
            row_shards = 1 if feat_parallel else self.num_machines
            if feat_parallel and self.rank > 0:
                # every rank loads the full file (num_shards=1), so only
                # rank 0 may write the .bin cache — concurrent writers would
                # truncate each other on a shared filesystem.  (Mutated
                # AFTER the config-fingerprint check, which already ran.)
                cfg.is_save_binary_file = False
            self.train_data = load_dataset(cfg.data, cfg, rank=row_rank,
                                           num_shards=row_shards)
            if self.boosting_old is not None:
                self._set_init_scores(self.train_data, cfg.data)
            reducers = None
            if self.num_machines > 1 and not feat_parallel:
                from .parallel.dist import make_metric_reducer
                reducers = make_metric_reducer()

            self.train_metrics = []
            for m in create_metrics(cfg):
                m.init("training", self.train_data.metadata,
                       self.train_data.num_data)
                if reducers is not None:
                    m.set_reducer(*reducers)
                self.train_metrics.append(m)

            self.valid_datas: List[Dataset] = []
            self.valid_metricss: List[List[Metric]] = []
            for fname in cfg.valid_data:
                # multi-host: valid files shard per rank like the train file;
                # metric reduction makes the reported values global
                vd = load_dataset(fname, cfg, reference=self.train_data,
                                  rank=row_rank, num_shards=row_shards)
                if self.boosting_old is not None:
                    self._set_init_scores(vd, fname)
                ms = []
                for m in create_metrics(cfg):
                    m.init(fname, vd.metadata, vd.num_data)
                    if reducers is not None:
                        m.set_reducer(*reducers)
                    ms.append(m)
                self.valid_datas.append(vd)
                self.valid_metricss.append(ms)
            loaded["rows"] = self.train_data.num_data
            loaded["features"] = self.train_data.num_features
        log.info("Finished loading data, %f seconds used" % loading.seconds)

        self.objective.init(self.train_data.metadata,
                            self.train_data.num_data)
        tm = self.train_metrics if cfg.is_training_metric else []
        self.boosting = create_boosting(cfg, self.train_data, self.objective,
                                        tm)
        if self.boosting_old is not None:
            # bring over the already-trained trees so saved models contain
            # the full ensemble
            self.boosting.models = list(self.boosting_old.models)
            self.boosting.num_used_model = (
                len(self.boosting.models) // cfg.num_class)
        for vd, ms in zip(self.valid_datas, self.valid_metricss):
            self.boosting.add_valid_data(vd, ms)
        if self.num_machines > 1:
            from .parallel.dist import vote_any
            self.boosting.stop_sync = vote_any
        # crash-safe snapshots + auto-resume (resilience/snapshot.py):
        # the manager rides save_checkpoint's bit-exact state; resume
        # must run AFTER the booster has its datasets/valid sets so the
        # restored state lands in the exact structures training uses
        from .resilience.snapshot import SnapshotManager
        if self._warm_start_ckpt is not None:
            # bit-exact warm start (init_model=<checkpoint>): the base
            # state loads first; a newer snapshot from THIS run's
            # snapshot_dir still wins below (it continues the same
            # lineage — load_checkpoint fingerprint-checks both)
            self.boosting.load_checkpoint(self._warm_start_ckpt)
            if self.boosting.iter > cfg.num_iterations:
                log.fatal("input_model=%s holds %d iterations, beyond "
                          "num_iterations=%d — the model would "
                          "silently contain more rounds than requested"
                          % (self._warm_start_ckpt,
                             int(self.boosting.iter),
                             cfg.num_iterations))
            log.info("Warm start from checkpoint %s (iteration %d)"
                     % (self._warm_start_ckpt, int(self.boosting.iter)))
        self.snapshots = SnapshotManager.from_config(
            cfg, self.rank, self.num_machines)
        if self.snapshots is not None:
            self.snapshots.maybe_resume(self.boosting)
        log.info("Finished initializing training")

    def _set_init_scores(self, ds, fname: str) -> None:
        from .io.parser import parse_file_lines

        lines: List[str] = []
        for src in self._init_score_sources(fname):
            with open(src) as f:
                # non-empty = any character, matching the native
                # scanner and the loader's row counting (a
                # whitespace-only line is a row)
                src_lines = [ln for ln in f.read().splitlines() if ln]
            if self.config.has_header:
                # per-source: every drop file carries its own header
                src_lines = src_lines[1:]
            lines.extend(src_lines)
        # dense width fixed to the OLD model's schema, like the
        # reference's Predictor-based init-score pass (predictor.hpp)
        w = max(self.boosting_old.max_feature_idx + 2, ds.label_idx + 1)
        _, feats, _ = parse_file_lines(lines, ds.label_idx, dense_cols=w)
        if ds.local_rows is not None:
            # rank-sharded dataset: predict only this rank's rows so the
            # init scores align with the local shard at 1/P the traversal
            # cost (add_valid_data's size check would otherwise silently
            # drop them)
            feats = feats[ds.local_rows]
        raw = self.boosting_old.predict_raw(feats)   # [K, N_local]
        ds.metadata.init_score = raw.reshape(-1).astype(np.float64)

    def _init_score_sources(self, fname: str) -> List[str]:
        """The text files whose rows (in order) make up `fname`'s rows:
        the file itself, or — when training continues over a freshly
        INGESTED shard directory (the refresh pipeline's incremental-
        boosting lane) — the manifest's source files.  The shard dir
        only holds BINNED values; the init-score pass predicts on raw
        features, so the sources must still exist."""
        from .ingest.manifest import (is_manifest_path, load_manifest,
                                      manifest_dir)
        if not is_manifest_path(fname):
            return [fname]
        m = load_manifest(manifest_dir(fname))
        if m is None:
            log.fatal("continued training from %s: no readable "
                      "manifest (re-run task=ingest)" % fname)
        missing = [s for s in m.sources if not os.path.isfile(s)]
        if missing:
            log.fatal("continued training from %s needs the original "
                      "text sources to predict init scores (shards "
                      "hold binned values only), but these moved: %s"
                      % (fname, ", ".join(missing)))
        return list(m.sources)

    def train(self) -> None:
        from .models.gbdt import NO_LIMIT

        cfg = self.config
        snaps = self.snapshots
        log.info("Started training...")
        start = time.time()
        is_finished = False
        # resume=auto restored the booster mid-run: continue counting
        # from ITS iteration (0 on a fresh start)
        it = int(self.boosting.iter)
        # graceful preemption: SIGTERM converts to "snapshot at the next
        # segment boundary, then exit cleanly" — a preemptible pool
        # loses at most one segment, not the job.  Handler installed
        # only while training (and only on the main thread).
        preempted = {"flag": False}

        def _on_term(signum: int, frame: Any) -> None:
            preempted["flag"] = True
            log.info("SIGTERM: snapshotting at the next segment "
                     "boundary, then exiting")

        prev_term: Any = None
        if snaps is not None and snaps.period > 0:
            try:
                prev_term = signal.signal(signal.SIGTERM, _on_term)
            except ValueError:   # not on the main thread (embedded use)
                prev_term = None
        try:
            # iteration-batched segments (config.iter_batch): the booster
            # scans K iterations per device dispatch and surfaces control
            # only at metric / early-stop / re-bagging boundaries.  Metric
            # lines and the final model are identical to the per-iteration
            # loop's; the incremental-save cadence and the elapsed-seconds
            # log timestamps become per-SEGMENT (up to K iterations between
            # appends — iter_batch=1 restores the per-iteration cadence)
            while it < cfg.num_iterations and not is_finished:
                is_finished, done = self.boosting.train_segment(
                    cfg.num_iterations - it)
                for j in range(done):
                    log.info("%f seconds elapsed, finished iteration %d"
                             % (time.time() - start, it + j + 1))
                it += done
                stop_now = preempted["flag"]
                if snaps is not None and snaps.period > 0 \
                        and self.num_machines > 1:
                    # one rank's SIGTERM stops EVERY rank at the same
                    # boundary.  Gated on period > 0 — the same
                    # fingerprint-synced config condition that installs
                    # the SIGTERM handler, so the collective runs
                    # symmetrically on all ranks and a resume-only
                    # manager (period=0) pays no per-segment allgather
                    stop_now = snaps.sync_flag(stop_now)
                if stop_now:
                    snaps.write(self.boosting)
                    # the incremental model save is mid-stream: drop
                    # its tmp (the resume run rewrites the model from
                    # the snapshot; an orphan would accumulate per
                    # preemption)
                    self.boosting.abort_model_save()
                    log.info("Preempted at iteration %d: snapshot "
                             "flushed, exiting cleanly" % it)
                    return
                self.boosting.save_model_to_file(NO_LIMIT, is_finished,
                                                 cfg.output_model)
                if snaps is not None and snaps.due(it):
                    snaps.write(self.boosting)
            self.boosting.save_model_to_file(NO_LIMIT, True,
                                             cfg.output_model)
        finally:
            if prev_term is not None:
                signal.signal(signal.SIGTERM, prev_term)
        log.info("Finished training")

    # ------------------------------------------------------------------
    def init_predict(self) -> None:
        from .models.gbdt import (GBDT, NO_LIMIT,
                                  boosting_type_from_model_file)

        cfg = self.config
        if not cfg.input_model:
            log.fatal("Need a model file for prediction (input_model)")
        import jax
        if jax.default_backend() != "cpu":
            # x64 lets the f64 score accumulation fuse into the device
            # dispatch (ops/predict.accumulate_scores): the per-chunk
            # readback shrinks from [C, T] leaf indices to [K, C]
            # doubles, bit-identically.  Prediction-only process, so no
            # training path sees the flag.
            jax.config.update("jax_enable_x64", True)
        btype = boosting_type_from_model_file(cfg.input_model)
        cfg.boosting_type = btype
        self.boosting = GBDT(cfg, None, None)
        with open(cfg.input_model) as f:
            self.boosting.load_model_from_string(f.read())
        self.boosting.set_num_used_model(
            cfg.num_model_predict * self.boosting.num_class
            if cfg.num_model_predict >= 0 else NO_LIMIT)

    # rows per streamed predict block; memory is bounded by this
    # regardless of input file size
    PREDICT_STREAM_ROWS = 1 << 16

    def predict(self) -> None:
        """Streaming file prediction.

        The reference streams the input in blocks with parse, predict and
        write overlapped across OpenMP threads (predictor.hpp:82-130,
        text_reader.h:214-290).  Here: a parse-ahead thread tokenizes
        block i+1 while block i runs the stacked-tree traversal on
        device, and formatted rows stream to the output file — bounded
        memory for arbitrarily large inputs, byte-identical output to the
        whole-file path (goldens in test_e2e_parity pin all three modes).
        """
        from concurrent.futures import ThreadPoolExecutor

        from .io.parser import parse_predict_rows
        from .predict_fast import format_pred_rows

        cfg = self.config
        log.info("Started prediction...")
        booster = self.boosting
        label_idx = booster.label_idx
        n_total_feat = booster.max_feature_idx + 1

        def blocks():
            buf = []
            with open(cfg.data) as f:
                # skip the first NON-blank line as the header, matching
                # _set_init_scores and io/dataset._skip_header
                skip = cfg.has_header
                for ln in f:
                    # same non-empty rule as the loader/native scanner:
                    # a line needs at least one non-EOL character (file
                    # iteration keeps the '\n', so `not ln` would never
                    # fire; whitespace-only lines ARE rows)
                    if not ln.strip("\r\n"):
                        continue
                    if skip:
                        skip = False
                        continue
                    buf.append(ln)
                    if len(buf) >= self.PREDICT_STREAM_ROWS:
                        yield buf
                        buf = []
            if buf:
                yield buf

        fmt = [None]

        def parse(feats_lines):
            # model-width parse shared with serving (the reference
            # Predictor's every-field + drop-past-num_features rule,
            # io/parser.parse_predict_rows)
            feats, f = parse_predict_rows(feats_lines, label_idx,
                                          n_total_feat, fmt[0])
            fmt[0] = f  # sniff once, reuse for every later block
            return feats

        def format_block(feats) -> bytes:
            # output formatting shared with serving
            # (predict_fast.format_pred_rows: native bulk %g /
            # tab-joined leaf ids)
            if cfg.is_predict_leaf_index:
                return format_pred_rows(
                    booster.predict_leaf_index(feats), True)  # [N, T]
            if cfg.is_predict_raw_score:
                res = booster.predict_raw(feats)             # [K, N]
            else:
                res = booster.predict(feats)
            return format_pred_rows(res, False)

        gen = blocks()
        # pull the first block BEFORE opening the output so an empty
        # input fatals without clobbering a previous result; the atomic
        # writer extends that guarantee to EVERY failure (a crash
        # mid-stream leaves the previous complete result, never a
        # truncated one — the tmp is replaced only on success)
        first = next(gen, None)
        if first is None:
            log.fatal("Data file %s is empty" % cfg.data)
        from .resilience.atomic import atomic_writer
        with atomic_writer(cfg.output_result) as out_f, \
                ThreadPoolExecutor(max_workers=1) as ex:
            pending = ex.submit(parse, first)
            for lines in gen:
                nxt = ex.submit(parse, lines)
                out_f.write(format_block(pending.result()))
                pending = nxt
            out_f.write(format_block(pending.result()))
        log.info("Finished prediction, results saved to %s"
                 % cfg.output_result)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    try:
        Application(argv).run()
    except Exception as ex:  # mirror main.cpp's catch-and-report
        sys.stderr.write("Met Exceptions:\n%s\n" % ex)
        return 1
    return 0
