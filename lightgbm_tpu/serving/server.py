"""`task=serve`: the warm-model HTTP prediction server.

Endpoints:
  POST /predict[?mode=normal|raw|leaf][&header=0|1]
        Body: rows in the task=predict data-file format (CSV/TSV/LibSVM,
        label column included at the model's label_index) or JSON
        feature rows ({"rows": [[...], ...]} / bare [[...]] — no label
        column, the c_api matrix-predict convention).  Response bytes
        are identical to what `task=predict` writes for the same rows
        (tests/test_serving.py pins it against the golden predict
        outputs).  A 0-row body returns an empty 200 body.
  GET  /healthz     liveness + loaded-model info (JSON)
  GET  /metrics     Prometheus text: request/row/batch counters,
                    latency + batch-size histograms, in-flight gauge
  POST /reload      atomic hot model swap: {"model": "<path>"} (default:
                    the configured input_model).  The new forest parses
                    and warms off to the side; in-flight requests finish
                    on the old forest (batches key on the forest object).

Graceful drain: SIGTERM/SIGINT stop the listener, finish queued
batches, then exit — no request is dropped mid-flight.

Everything is stdlib (http.server threading model: one handler thread
per connection, blocked in MicroBatcher.submit while its rows ride a
coalesced dispatch).
"""

from __future__ import annotations

__jax_free__ = True

import json
import os
import signal
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Sequence, Tuple
from urllib.parse import ParseResult, parse_qs, urlparse

import numpy as np

from ..analysis.contracts import contract
from ..config import Config
from ..io.parser import parse_predict_rows, sniff_format
from ..resilience.faults import faultpoint
from ..utils import log
from .batcher import BatcherClosed, MicroBatcher, RowsPayload, TextPayload
from .fleet import ModelFleet, UnknownModelError
from .forest import MODES, ServingForest, load_forest

MAX_BODY_BYTES = 256 << 20   # refuse absurd request bodies outright


# ---------------------------------------------------------------------------
# Prometheus metrics (text exposition format, no client library needed)
# ---------------------------------------------------------------------------

# sub-ms buckets lead: the low-latency lane answers single rows in
# tens-to-hundreds of microseconds, and a histogram whose first bucket
# is 1 ms reports every such request as "<= 0.001" — invisible p99
_LATENCY_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                    10.0)
_BATCH_ROW_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
                      2048, 4096, 8192, 16384)


class _Histogram:
    def __init__(self, buckets: Sequence[float]):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)   # +inf tail
        self.sum = 0.0

    @contract.locked_by("_lock")
    def observe(self, v: float) -> None:
        # _Histogram is an internal of Metrics: graftcheck GC004
        # verifies every observe() call site holds Metrics._lock (the
        # threaded test_serving_metrics_locking regression hammers it)
        self.sum += v
        for i, b in enumerate(self.buckets):
            if v <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def render(self, name: str, help_: str, out: List[str],
               labels: str = "", with_meta: bool = True) -> None:
        """`labels` ('lane="fast"') renders a labeled series; families
        with several labeled histograms emit HELP/TYPE once
        (with_meta on the first call only)."""
        if with_meta:
            out.append("# HELP %s %s" % (name, help_))
            out.append("# TYPE %s histogram" % name)
        pre = labels + "," if labels else ""
        wrap = ("{%s}" % labels) if labels else ""
        cum = 0
        for b, c in zip(self.buckets, self.counts):
            cum += c
            out.append('%s_bucket{%sle="%g"} %d' % (name, pre, b, cum))
        cum += self.counts[-1]
        out.append('%s_bucket{%sle="+Inf"} %d' % (name, pre, cum))
        out.append("%s_sum%s %.17g" % (name, wrap, self.sum))
        out.append("%s_count%s %d" % (name, wrap, cum))


class Metrics:
    """Thread-safe serving metrics, rendered in Prometheus text format."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.started_at = time.time()
        self.requests: Dict[Tuple[str, int], int] = {}
        # per-model predict accounting, keyed (source, sha12): fleet
        # probes and dashboards can tell WHICH model served the traffic
        self.model_requests: Dict[Tuple[str, str], int] = {}
        self.model_rows: Dict[Tuple[str, str], int] = {}
        self.rows_total = 0
        self.batches_total = 0
        self.reloads_total = 0
        self.reload_failures_total = 0
        self.dispatch_failures_total = 0
        self.overload_rejected_total = 0
        self.in_flight = 0
        self.latency = _Histogram(_LATENCY_BUCKETS)
        self.batch_rows = _Histogram(_BATCH_ROW_BUCKETS)
        # per-lane routing observability (serve_low_latency): request
        # counts + latency histograms keyed by admission lane, so the
        # fast-vs-batch decision — and what each lane's tail looks
        # like — is scrapeable instead of inferred
        self.lane_requests: Dict[str, int] = {"fast": 0, "batch": 0}
        self.lane_latency: Dict[str, _Histogram] = {
            "fast": _Histogram(_LATENCY_BUCKETS),
            "batch": _Histogram(_LATENCY_BUCKETS)}

    @contract.locked_by("_lock")
    def _lane_observe(self, lane: str, seconds: float) -> None:
        # lane state shares Metrics._lock with every histogram:
        # graftcheck GC004 verifies each call site holds it
        self.lane_requests[lane] = self.lane_requests.get(lane, 0) + 1
        self.lane_latency[lane].observe(seconds)

    def request_started(self, endpoint: str) -> None:
        # the gauge tracks PREDICT work in flight; a /metrics scrape
        # must not count itself
        if endpoint == "/predict":
            with self._lock:
                self.in_flight += 1

    def request_finished(self, endpoint: str, code: int,
                         seconds: float, rows: int = 0,
                         model: Optional[Tuple[str, str]] = None,
                         lane: Optional[str] = None) -> None:
        with self._lock:
            if endpoint == "/predict":
                self.in_flight -= 1
            key = (endpoint, code)
            self.requests[key] = self.requests.get(key, 0) + 1
            self.rows_total += rows
            if model is not None:
                self.model_requests[model] = \
                    self.model_requests.get(model, 0) + 1
                self.model_rows[model] = \
                    self.model_rows.get(model, 0) + rows
            if endpoint == "/predict" and code == 200:
                self.latency.observe(seconds)
                if lane is not None:
                    self._lane_observe(lane, seconds)

    def batch_dispatched(self, n_items: int, n_rows: int) -> None:
        with self._lock:
            self.batches_total += 1
            self.batch_rows.observe(n_rows)

    def reloaded(self) -> None:
        with self._lock:
            self.reloads_total += 1

    def reload_failed(self) -> None:
        with self._lock:
            self.reload_failures_total += 1

    def dispatch_failed(self) -> None:
        with self._lock:
            self.dispatch_failures_total += 1

    def overload_rejected(self) -> None:
        with self._lock:
            self.overload_rejected_total += 1

    def render(self, forest: ServingForest, degraded: bool = False,
               inflight_rows: int = 0,
               models: Optional[List[Dict[str, Any]]] = None,
               worker: Optional[Tuple[int, int]] = None,
               queue_depth: int = 0) -> bytes:
        """Prometheus text.  `forest` is the DEFAULT model (its gauges
        keep their historical unlabeled names); `models` is the fleet
        listing (per-model labeled series); `worker` is (index, pid)
        when this process runs behind the multi-process front-end;
        `queue_depth` is the batcher's live segment count."""
        out: List[str] = []
        with self._lock:
            out.append("# HELP lgbm_serve_requests_total "
                       "HTTP requests by endpoint and status code")
            out.append("# TYPE lgbm_serve_requests_total counter")
            for (ep, code), n in sorted(self.requests.items()):
                out.append('lgbm_serve_requests_total{endpoint="%s",'
                           'code="%d"} %d' % (ep, code, n))
            out.append("# HELP lgbm_serve_rows_total "
                       "prediction rows served")
            out.append("# TYPE lgbm_serve_rows_total counter")
            out.append("lgbm_serve_rows_total %d" % self.rows_total)
            out.append("# HELP lgbm_serve_model_requests_total "
                       "predict requests by served model")
            out.append("# TYPE lgbm_serve_model_requests_total counter")
            for (src, sha), n in sorted(self.model_requests.items()):
                out.append('lgbm_serve_model_requests_total'
                           '{model="%s",sha="%s"} %d' % (src, sha, n))
            out.append("# HELP lgbm_serve_model_rows_total "
                       "prediction rows by served model")
            out.append("# TYPE lgbm_serve_model_rows_total counter")
            for (src, sha), n in sorted(self.model_rows.items()):
                out.append('lgbm_serve_model_rows_total'
                           '{model="%s",sha="%s"} %d' % (src, sha, n))
            out.append("# HELP lgbm_serve_batches_total "
                       "coalesced predict dispatches")
            out.append("# TYPE lgbm_serve_batches_total counter")
            out.append("lgbm_serve_batches_total %d" % self.batches_total)
            out.append("# HELP lgbm_serve_reloads_total "
                       "successful hot model swaps")
            out.append("# TYPE lgbm_serve_reloads_total counter")
            out.append("lgbm_serve_reloads_total %d" % self.reloads_total)
            out.append("# HELP lgbm_serve_reload_failures_total "
                       "failed /reload attempts (old model kept serving)")
            out.append("# TYPE lgbm_serve_reload_failures_total counter")
            out.append("lgbm_serve_reload_failures_total %d"
                       % self.reload_failures_total)
            out.append("# HELP lgbm_serve_dispatch_failures_total "
                       "device-dispatch failures answered on the "
                       "native fallback")
            out.append("# TYPE lgbm_serve_dispatch_failures_total counter")
            out.append("lgbm_serve_dispatch_failures_total %d"
                       % self.dispatch_failures_total)
            out.append("# HELP lgbm_serve_overload_rejected_total "
                       "predict requests shed with 503 + Retry-After "
                       "by admission control")
            out.append("# TYPE lgbm_serve_overload_rejected_total counter")
            out.append("lgbm_serve_overload_rejected_total %d"
                       % self.overload_rejected_total)
            out.append("# HELP lgbm_serve_degraded "
                       "1 when the circuit breaker pinned serving to "
                       "the JAX-free native predictor")
            out.append("# TYPE lgbm_serve_degraded gauge")
            out.append("lgbm_serve_degraded %d" % int(degraded))
            out.append("# HELP lgbm_serve_lane_requests_total "
                       "predict requests by admission lane (fast = "
                       "synchronous low-latency dispatch, batch = "
                       "coalesced micro-batch)")
            out.append("# TYPE lgbm_serve_lane_requests_total counter")
            for lane in sorted(self.lane_requests):
                out.append('lgbm_serve_lane_requests_total{lane="%s"} %d'
                           % (lane, self.lane_requests[lane]))
            out.append("# HELP lgbm_serve_batcher_queue_depth "
                       "request segments waiting in the micro-batcher "
                       "queue")
            out.append("# TYPE lgbm_serve_batcher_queue_depth gauge")
            out.append("lgbm_serve_batcher_queue_depth %d" % queue_depth)
            out.append("# HELP lgbm_serve_in_flight "
                       "requests currently being handled")
            out.append("# TYPE lgbm_serve_in_flight gauge")
            out.append("lgbm_serve_in_flight %d" % self.in_flight)
            out.append("# HELP lgbm_serve_inflight_rows "
                       "admitted prediction rows currently in flight")
            out.append("# TYPE lgbm_serve_inflight_rows gauge")
            out.append("lgbm_serve_inflight_rows %d" % inflight_rows)
            out.append("# HELP lgbm_serve_model_loaded_timestamp_seconds "
                       "unix time the live model was loaded")
            out.append("# TYPE lgbm_serve_model_loaded_timestamp_seconds "
                       "gauge")
            # %.17g, not %g: a unix timestamp needs ~16 significant
            # digits ("%g" truncates to ~hours-of-error, breaking any
            # model-staleness alert computed from this gauge)
            out.append("lgbm_serve_model_loaded_timestamp_seconds %.17g"
                       % forest.loaded_at)
            out.append("# HELP lgbm_serve_model_num_trees "
                       "tree count of the live model")
            out.append("# TYPE lgbm_serve_model_num_trees gauge")
            out.append("lgbm_serve_model_num_trees %d" % forest.num_models)
            if models:
                # fleet identity: one series per WARM model, labeled
                # with path + content sha so dashboards can tell which
                # model each worker actually serves
                out.append("# HELP lgbm_serve_fleet_model_loaded_"
                           "timestamp_seconds unix load time per warm "
                           "fleet model")
                out.append("# TYPE lgbm_serve_fleet_model_loaded_"
                           "timestamp_seconds gauge")
                for doc in models:
                    if not doc.get("warm"):
                        continue
                    out.append(
                        'lgbm_serve_fleet_model_loaded_timestamp_seconds'
                        '{model="%s",sha="%s",default="%d"} %.17g'
                        % (doc["source"], str(doc["sha"])[:12],
                           int(bool(doc.get("default"))),
                           doc["loaded_at"]))
                # model age: the staleness signal refresh dashboards
                # alert on (a stuck deploy agent shows up as the
                # default model's age climbing past the cadence)
                out.append("# HELP lgbm_serve_model_age_seconds "
                           "seconds since each warm fleet model was "
                           "loaded")
                out.append("# TYPE lgbm_serve_model_age_seconds gauge")
                now = time.time()
                for doc in models:
                    if not doc.get("warm"):
                        continue
                    out.append(
                        'lgbm_serve_model_age_seconds'
                        '{model="%s",sha="%s",default="%d"} %.3f'
                        % (doc["source"], str(doc["sha"])[:12],
                           int(bool(doc.get("default"))),
                           max(0.0, now - doc["loaded_at"])))
            if worker is not None:
                # multi-process front-end: which worker answered this
                # scrape, and that it is alive — repeated scrapes land
                # on different workers (SO_REUSEPORT picks per
                # connection), so a prober sees the whole fleet
                out.append("# HELP lgbm_serve_worker front-end worker "
                           "liveness (the worker that answered this "
                           "scrape)")
                out.append("# TYPE lgbm_serve_worker gauge")
                out.append('lgbm_serve_worker{index="%d",pid="%d"} 1'
                           % worker)
            self.latency.render("lgbm_serve_request_latency_seconds",
                                "predict request latency", out)
            for i, lane in enumerate(sorted(self.lane_latency)):
                self.lane_latency[lane].render(
                    "lgbm_serve_lane_latency_seconds",
                    "predict request latency by admission lane", out,
                    labels='lane="%s"' % lane, with_meta=(i == 0))
            self.batch_rows.render("lgbm_serve_batch_rows",
                                   "rows per coalesced dispatch", out)
        return ("\n".join(out) + "\n").encode()


# ---------------------------------------------------------------------------
# Request body -> batcher payload
# ---------------------------------------------------------------------------

class BadRequest(ValueError):
    status = 400


class LengthRequired(BadRequest):
    status = 411


def _error_json(ex: BaseException) -> bytes:
    """Structured error body: {"error": <class>, "message": <str>} —
    machine-parseable by clients and load balancers instead of a bare
    status line."""
    return (json.dumps({"error": type(ex).__name__,
                        "message": str(ex)}) + "\n").encode()


def _strip_first_line(text: bytes) -> bytes:
    """Drop the first non-blank line (request-level has_header)."""
    pos = 0
    while pos < len(text):
        eol = text.find(b"\n", pos)
        end = len(text) if eol < 0 else eol
        if text[pos:end].strip(b"\r"):
            return text[end + 1:] if eol >= 0 else b""
        if eol < 0:
            break
        pos = eol + 1
    return b""


def _parse_json_rows(body: bytes) -> np.ndarray:
    try:
        doc = json.loads(body.decode("utf-8"))
    except (ValueError, UnicodeDecodeError) as ex:
        raise BadRequest("invalid JSON body: %s" % ex)
    rows = doc.get("rows") if isinstance(doc, dict) else doc
    if not isinstance(rows, list):
        raise BadRequest('JSON body must be {"rows": [[...], ...]} '
                         "or a bare list of rows")
    if not rows:
        return np.zeros((0, 0), dtype=np.float64)
    try:
        feats = np.asarray(rows, dtype=np.float64)
    except (TypeError, ValueError) as ex:
        raise BadRequest("rows must be numeric lists: %s" % ex)
    if feats.ndim != 2:
        raise BadRequest("rows must be a list of equal-length lists")
    return feats


def _parse_text_rows(body: bytes, forest: ServingForest) -> np.ndarray:
    """Data-file lines -> [N, F_model] f64 through the SAME model-width
    parse as cli.predict (io/parser.parse_predict_rows)."""
    lines = [ln for ln in body.decode("utf-8", "replace").splitlines()
             if ln.strip("\r")]
    n_total_feat = forest.max_feature_idx + 1
    if not lines:
        return np.zeros((0, n_total_feat), dtype=np.float64)
    feats, _ = parse_predict_rows(lines, forest.label_idx, n_total_feat)
    return feats


def _sniff_sep(body: bytes) -> Tuple[str, str]:
    """(fmt, sep) for a request body via the SHARED complete-lines
    sniff (io/parser.sniff_format, same rule as the predict fast
    path's file sniff — the two cannot drift)."""
    chunks = iter((body,))
    return sniff_format(lambda: next(chunks, b""))


def _estimate_rows(body: bytes, is_json: bool) -> int:
    """Cheap row estimate for admission control BEFORE any parse work:
    shedding must not burn parse CPU/memory on requests it is about to
    503.  Text bodies are one row per line, counted under the SAME
    universal line endings splitlines() honors — a bare-'\\r' body
    must not estimate ~0 rows and slip a huge parse past admission.
    JSON rows are one '['-opened list each, plus one for the enclosing
    list.  The admitted count is trued up to the parsed row count
    afterwards, so the estimate only has to be close."""
    if is_json:
        return max(0, body.count(b"[") - 1)
    return (body.count(b"\n") + body.count(b"\r")
            - body.count(b"\r\n"))


# ---------------------------------------------------------------------------
# Serving state: forest + batcher + metrics, hot-swappable
# ---------------------------------------------------------------------------

class ServingState:
    def __init__(self, cfg: Config, forest: ServingForest,
                 worker_index: Optional[int] = None):
        self.cfg = cfg
        self.metrics = Metrics()
        self.fleet = ModelFleet(cfg, forest)
        self.worker_index = worker_index     # multi-process front-end
        self._swap_lock = threading.Lock()   # serializes /reload only
        self.draining = False
        # admission control (degrade-don't-die): bounded in-flight ROWS
        # — past the bound new requests get a fast 503 + Retry-After
        # instead of queueing without bound in the batcher
        self.max_inflight_rows = cfg.serve_max_inflight_rows
        self.retry_after_s = cfg.serve_retry_after_s
        self._adm_lock = threading.Lock()
        self._inflight_rows = 0
        # circuit breaker: consecutive device-dispatch failures before
        # a forest pins itself to the JAX-free native predictor.  The
        # streak is PER FOREST (keyed by its explicit identity): one
        # healthy fleet model's successes must not reset — or its
        # degradation block — another model's breaker
        self.breaker_threshold = cfg.serve_breaker_threshold
        self._breaker_lock = threading.Lock()
        self._dispatch_failures: Dict[Tuple[str, int], int] = {}
        # whether the streak above saw a matmul-routed failure: stage 1
        # (disable matmul) only makes sense when matmul is implicated
        self._streak_saw_matmul: Dict[Tuple[str, int], bool] = {}
        # latency-class admission lane (serve_low_latency): requests at
        # or below the effective row bound never enter the batcher —
        # they dispatch synchronously on the jax-free flat-table engine
        # (or the fused native kernel for text), so a single row never
        # waits out a coalescing window behind a forming batch.  auto
        # clamps the bound below the matmul threshold so the lane can
        # never eat a batch the device route is configured to serve
        # (=on with a contradictory bound is a config-load fatal).
        lane_rows = cfg.serve_low_latency_max_rows
        if cfg.serve_low_latency == "auto":
            lane_rows = min(lane_rows, cfg.serve_matmul_min_rows - 1)
        self.lane_max_rows = (0 if cfg.serve_low_latency == "off"
                              else max(0, lane_rows))
        self.batcher = MicroBatcher(
            self._run_batch, cfg.serve_max_batch_rows,
            cfg.serve_batch_timeout_ms,
            on_batch=self.metrics.batch_dispatched)

    @property
    def forest(self) -> ServingForest:
        """The DEFAULT model's warm forest (single-model callers)."""
        return self.fleet.default()

    @property
    def degraded(self) -> bool:
        """Breaker state DERIVED from the live pool: degraded while any
        currently-pooled forest is host-pinned.  Replacing the degraded
        instance (reload of ITS path) clears it; reloading an unrelated
        fleet model does not falsely report recovery."""
        return any(f.degraded for f in self.fleet.warm_models())

    def forest_for(self, model: Optional[str]) -> ServingForest:
        """Fleet routing: /predict?model=<path> -> that registered
        model's warm forest (loaded + warmed on first use)."""
        return self.fleet.get(model)

    @property
    def inflight_rows(self) -> int:
        with self._adm_lock:
            return self._inflight_rows

    # -- admission control ---------------------------------------------
    def try_admit(self, nrows: int) -> bool:
        """Admit `nrows` against the in-flight budget.  An idle server
        always admits (a single oversized request still gets served —
        the batcher splits it); under load, anything that would push
        past the bound is shed."""
        with self._adm_lock:
            if self._inflight_rows > 0 \
                    and self._inflight_rows + nrows \
                    > self.max_inflight_rows:
                return False
            self._inflight_rows += nrows
            return True

    def release(self, nrows: int) -> None:
        with self._adm_lock:
            self._inflight_rows -= nrows

    # -- circuit breaker ------------------------------------------------
    def _guarded_predict(self, forest: ServingForest, batch: Any,
                         mode: str) -> Any:
        """Device predict with degrade-don't-die semantics, in ORDER
        matmul -> descent -> native: a failed matmul dispatch answers
        THIS batch on the descent route (still the device, whose bucket
        warm() pre-compiled), a failed descent answers on the JAX-free
        host path — byte-identical all three ways (tests pin route and
        engine parity).  After `breaker_threshold` consecutive failures
        the breaker degrades one stage: first it pins the forest to the
        descent route (disable_matmul), then to the host engine, until
        /reload builds a fresh forest."""
        if forest.engine != "jax":
            return forest.predict(batch, mode)
        routed_mm = forest.matmul_routed(batch.shape[0])
        try:
            res = forest.predict(batch, mode)
        except log.LightGBMError:
            raise              # data error: the client's fault, not the device's
        except Exception as ex:
            self._dispatch_failure(forest, ex, routed_mm=routed_mm)
            if routed_mm:
                # stage-1 fallback: the descent executable for this
                # bucket exists (warm compiled both routes), so answer
                # on the device before giving up on it entirely
                try:
                    return forest.predict(batch, mode, route="descent")
                except log.LightGBMError:
                    raise
                except Exception as ex2:
                    self._dispatch_failure(forest, ex2)
            return forest.predict(batch, mode, engine="host")
        with self._breaker_lock:
            self._dispatch_failures.pop(forest.identity, None)
            self._streak_saw_matmul.pop(forest.identity, None)
        return res

    def _dispatch_failure(self, forest: ServingForest,
                          ex: BaseException,
                          routed_mm: bool = False) -> None:
        """Count one device-dispatch failure against THIS forest's
        streak; `routed_mm` says which route the failed dispatch took.
        Stage 1 (disable matmul) only fires when the streak implicates
        the matmul route — a pure descent-failure streak (e.g. all
        traffic below serve_matmul_min_rows) goes straight to the host
        pin instead of wasting a threshold window turning off a route
        that never ran."""
        self.metrics.dispatch_failed()
        with self._breaker_lock:
            # in-flight batches stay pinned to a pre-/reload (or
            # evicted) forest by design: their failures must not count
            # against the breaker on the live pool
            if not self.fleet.contains(forest):
                n, trip = 0, False
            else:
                key = forest.identity
                n = self._dispatch_failures.get(key, 0) + 1
                self._dispatch_failures[key] = n
                saw_mm = self._streak_saw_matmul.get(key, False) \
                    or routed_mm
                self._streak_saw_matmul[key] = saw_mm
                trip = n >= self.breaker_threshold \
                    and not forest.degraded
                if trip and saw_mm and forest.matmul_live():
                    # stage 1: matmul -> descent, this forest's counter
                    # restarts; a further streak takes the final stage
                    self._dispatch_failures[key] = 0
                    self._streak_saw_matmul[key] = False
                    forest.disable_matmul()
                    log.warning(
                        "serve: circuit breaker stage 1 after %d "
                        "consecutive device-dispatch failures — matmul "
                        "route disabled, serving on the stacked "
                        "descent" % n)
                    trip = False
        log.warning("serve: device dispatch failed (%s: %s); answered "
                    "on the fallback path" % (type(ex).__name__, ex))
        if trip:
            forest.degrade()
            log.warning("serve: circuit breaker OPEN after %d "
                        "consecutive device-dispatch failures — "
                        "serving on the JAX-free native predictor "
                        "until /reload" % n)

    # -- the low-latency lane (synchronous, handler thread) ------------
    def fast_lane(self, nrows: int) -> bool:
        """Admission-lane routing: does an nrows request bypass the
        coalescing window?"""
        return nrows <= self.lane_max_rows

    def fast_predict(self, forest: ServingForest, payload: Any,
                     mode: str) -> List[bytes]:
        """One request answered NOW, on the handler thread: no batcher
        queue, no coalescing wait, no device dispatch.  Text bodies
        take the fused native kernel (parse -> descend -> format in
        one pass — the single-row fast path); parsed rows take the
        flat-table descent.  Both are jax-free and byte-identical to
        the batch path by construction (the flat table ranks against
        the same threshold tables as the device packs), so lane
        routing can never change a response byte."""
        if isinstance(payload, TextPayload):
            if payload.nrows:
                try:
                    got = forest.predict_text(payload.text, payload.fmt,
                                              payload.sep, mode)
                except log.LightGBMError:
                    # malformed token: redo on the parse path below so
                    # the error surfaces exactly like the batch path's
                    # per-item isolation
                    got = None
                if got is not None:
                    return [got[0]]
            # no native kernel (or 0 rows): parse + flat descent, the
            # same fallback order as the batch path's text dispatch
            feats = _parse_text_rows(payload.text, forest)
            res = forest.predict(feats, mode, engine="flat")
            return [forest.format_rows(res, mode)]
        feats = forest.fit_width(payload.feats)
        res = forest.predict(feats, mode, engine="flat")
        return [forest.format_rows(res, mode)]

    # -- the coalesced dispatch (MicroBatcher worker thread) -----------
    # Batches key on (forest, mode, family): the forest object isolates
    # hot-swap in-flight traffic, and the family keeps text requests of
    # different formats (csv vs tsv vs libsvm) — which cannot share one
    # native pass — out of each other's dispatches.
    def _run_batch(self, key: Any, payloads: Sequence[Any]) -> List[Any]:
        forest, mode, family = key
        if family[0] == "text":
            total = sum(p.nrows for p in payloads)
            if total:
                fmt, sep = family[1], family[2]
                try:
                    # host engine: ONE fused native pass over the joined
                    # request lines (each payload's text is newline-
                    # terminated by construction)
                    got = forest.predict_text(
                        b"".join(p.text for p in payloads), fmt, sep,
                        mode)
                except log.LightGBMError:
                    # a malformed token somewhere in the batch: redo
                    # per item below so only the offender fails
                    got = None
                if got is not None:
                    blob, rows = got
                    if rows != total:
                        raise RuntimeError(
                            "native predict returned %d rows for %d "
                            "input lines" % (rows, total))
                    return _split_lines(blob,
                                        [p.nrows for p in payloads])
            # no native kernel, 0 rows, or isolating a bad request:
            # parse + numeric path per item (errors stay per-item)
            out: List = []
            for p in payloads:
                try:
                    feats = _parse_text_rows(p.text, forest)
                    res = forest.predict(feats, mode)
                    out.append(forest.format_rows(res, mode))
                except log.LightGBMError as ex:
                    out.append(ex)
            return out
        feats = [forest.fit_width(p.feats) for p in payloads]
        counts = [f.shape[0] for f in feats]
        batch = (np.concatenate(feats, axis=0) if len(feats) > 1
                 else feats[0])
        res = self._guarded_predict(forest, batch, mode)
        blob = forest.format_rows(res, mode)
        return _split_lines(blob, counts)

    # -- hot swap -------------------------------------------------------
    def reload(self, model_path: str, make_default: bool = True,
               register_new: bool = False) -> Dict[str, Any]:
        """Parse + warm the new model OFF TO THE SIDE, then swap it
        into the fleet atomically: ANY failure in here (unreadable
        path, parse error, warm-up crash — the reload.parse faultpoint
        simulates them) propagates BEFORE the swap, so the old forest
        keeps serving untouched.  make_default repoints the default
        model at the new path (the single-model /reload semantics);
        make_default=False with register_new is the deploy agent's
        challenger push (body {"model":..,"default":false} — registers
        + warms WITHOUT promotion); plain make_default=False is the
        fleet's per-model in-place reload (/reload?model=<path>),
        leaving the default alone."""
        with self._swap_lock:
            old = self.fleet.default()
            was_degraded = self.degraded

            def loader(path: str) -> ServingForest:
                faultpoint("reload.parse")
                fresh = load_forest(
                    path,
                    num_model_predict=self.cfg.num_model_predict,
                    backend=self.cfg.serve_backend,
                    matmul=self.cfg.serve_matmul,
                    matmul_min_rows=self.cfg.serve_matmul_min_rows,
                    device_type=self.cfg.device_type)
                fresh.warm(self.cfg.serve_max_batch_rows)
                return fresh

            fresh = self.fleet.reload(model_path,
                                      make_default=make_default,
                                      loader=loader,
                                      register=register_new)
            # in-flight batches keep keying on the old instance.  The
            # degraded flag is DERIVED from the pool, so swapping a
            # degraded instance out is what closes its breaker; prune
            # failure streaks for forests no longer pooled
            with self._breaker_lock:
                live = {f.identity for f in self.fleet.warm_models()}
                self._dispatch_failures = {
                    k: v for k, v in self._dispatch_failures.items()
                    if k in live}
                self._streak_saw_matmul = {
                    k: v for k, v in self._streak_saw_matmul.items()
                    if k in live}
            if was_degraded and not self.degraded:
                log.info("serve: circuit breaker closed by /reload")
            self.metrics.reloaded()
            log.info("Hot-swapped model %s (%d trees) -> %s (%d trees)%s"
                     % (old.source, old.num_models, fresh.source,
                        fresh.num_models,
                        "" if make_default else " [fleet entry]"))
            return fresh.info()


def _split_lines(blob: bytes, counts: List[int]) -> List[bytes]:
    """Split newline-terminated output back per request segment (every
    predict mode emits exactly one line per row)."""
    parts: List[bytes] = []
    pos = 0
    for c in counts:
        if c == 0:
            parts.append(b"")
            continue
        end = pos
        for _ in range(c):
            nl = blob.find(b"\n", end)
            if nl < 0:
                end = len(blob)
                break
            end = nl + 1
        parts.append(blob[pos:end])
        pos = end
    return parts


# ---------------------------------------------------------------------------
# HTTP layer
# ---------------------------------------------------------------------------

def _make_handler(state: ServingState) -> type:
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # one buffered write per response + TCP_NODELAY: the default
        # unbuffered wfile emits headers as separate segments, and
        # Nagle x delayed-ACK turns that into ~40 ms per keep-alive
        # round trip on loopback (measured: p50 42 ms -> sub-10 ms)
        wbufsize = 1 << 16
        disable_nagle_algorithm = True

        def log_message(self, fmt: str, *args: Any) -> None:  # route through our logger
            log.debug("serve: " + fmt % args)

        def _respond(self, code: int, body: bytes,
                     ctype: str = "text/plain; charset=utf-8",
                     headers: Optional[Dict[str, str]] = None) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _body(self) -> bytes:
            if "chunked" in (self.headers.get("Transfer-Encoding")
                             or "").lower():
                # we only read Content-Length bodies; an unread chunked
                # body would desync the next keep-alive request, so
                # refuse AND drop the connection after responding
                # graftlint: disable=GL006 -- per-connection handler
                # state: one thread per connection, nothing shared
                self.close_connection = True
                raise LengthRequired(
                    "chunked request bodies are not supported; send "
                    "Content-Length")
            raw = (self.headers.get("Content-Length") or "0").strip()
            try:
                n = int(raw)
            except ValueError:
                n = -1   # force the refusal path below
            if n < 0 or n > MAX_BODY_BYTES:
                # a negative length would make rfile.read() block until
                # the client disconnects (read-to-EOF on the socket),
                # pinning the handler thread and the in-flight gauge;
                # garbage/absurd lengths are client faults.  Body
                # unread either way: the connection must drop.
                # graftlint: disable=GL006 -- per-connection handler
                # state: one thread per connection, nothing shared
                self.close_connection = True
                raise BadRequest(
                    "invalid or oversized Content-Length %r" % raw)
            return self.rfile.read(n) if n else b""

        # -- GET ---------------------------------------------------------
        def do_GET(self) -> None:
            t0 = time.monotonic()
            path = urlparse(self.path).path
            state.metrics.request_started(path)
            code = 200
            try:
                if path == "/healthz":
                    # degraded is a LIVE state worth alerting on, but
                    # the server still answers correctly (native
                    # fallback) — hence 200, with the status string
                    # carrying the distinction
                    status = ("draining" if state.draining
                              else "degraded" if state.degraded
                              else "ok")
                    doc = {"status": status,
                           "degraded": state.degraded,
                           "uptime_s": round(
                               time.time() - state.metrics.started_at, 3),
                           "model": state.forest.info(),
                           "models": state.fleet.info()}
                    if state.worker_index is not None:
                        # count included so a deploy agent knows how
                        # many per-connection-routed workers it must
                        # see confirm a push before calling it done
                        doc["worker"] = {"index": state.worker_index,
                                         "pid": os.getpid(),
                                         "count":
                                             state.cfg.serve_workers}
                    self._respond(200, json.dumps(doc).encode(),
                                  "application/json")
                elif path == "/metrics":
                    worker = (None if state.worker_index is None
                              else (state.worker_index, os.getpid()))
                    self._respond(
                        200, state.metrics.render(
                            state.forest, degraded=state.degraded,
                            inflight_rows=state.inflight_rows,
                            models=state.fleet.info(), worker=worker,
                            queue_depth=state.batcher.queue_depth()),
                        "text/plain; version=0.0.4; charset=utf-8")
                else:
                    code = 404
                    self._respond(404, b"not found\n")
            finally:
                state.metrics.request_finished(path, code,
                                               time.monotonic() - t0)

        # -- POST --------------------------------------------------------
        def do_POST(self) -> None:
            t0 = time.monotonic()
            url = urlparse(self.path)
            path = url.path
            state.metrics.request_started(path)
            code, rows = 200, 0
            model: Optional[Tuple[str, str]] = None
            lane: Optional[str] = None
            try:
                if path == "/predict":
                    code, rows, model, lane = self._predict(url)
                elif path == "/reload":
                    code = self._reload(url)
                else:
                    code = 404
                    self._respond(404, b"not found\n")
            except (BadRequest, log.LightGBMError) as ex:
                # LightGBMError here is a data error (e.g. an unknown
                # token while parsing the request body): client fault.
                # Structured body: error class + message, not a bare
                # status line.
                code = getattr(ex, "status", 400)
                self._respond(code, _error_json(ex), "application/json")
            except Exception as ex:
                code = 500
                log.warning("serve: internal error: %s" % ex)
                self._respond(500, _error_json(ex), "application/json")
            finally:
                state.metrics.request_finished(path, code,
                                               time.monotonic() - t0,
                                               rows, model=model,
                                               lane=lane)

        def _predict(self, url: ParseResult) \
                -> Tuple[int, int, Optional[Tuple[str, str]],
                         Optional[str]]:
            # read the body FIRST even on early-exit paths: an unread
            # body desyncs the next request on a keep-alive connection
            body = self._body()
            retry_hdr = {"Retry-After":
                         "%d" % max(1, round(state.retry_after_s))}
            if state.draining:
                self._respond(503, _error_json(
                    RuntimeError("draining")), "application/json",
                    headers=retry_hdr)
                return 503, 0, None, None
            q = parse_qs(url.query)
            mode = q.get("mode", ["normal"])[0].lower()
            if mode not in MODES:
                raise BadRequest("unknown mode %r (expect normal|raw|"
                                 "leaf)" % mode)
            ctype = (self.headers.get("Content-Type") or "").lower()
            try:
                # fleet routing: ?model=<registered path> — then pin
                # that ONE forest instance for the whole request
                forest = state.forest_for(q.get("model", [None])[0])
            except UnknownModelError as ex:
                raise BadRequest(
                    "unknown model %s (registered: %s)"
                    % (ex.args[0],
                       ", ".join(state.fleet.registered_paths())))
            mlabel = (forest.source, forest.content_sha[:12])
            is_json = "json" in ctype
            if not is_json:
                has_header = _qbool(q, "header", state.cfg.has_header)
                if has_header:
                    body = _strip_first_line(body)
                if body and not body.endswith(b"\n"):
                    body += b"\n"
            # admission control BEFORE parsing: shed load FAST (503 +
            # Retry-After) instead of queueing without bound — and
            # without paying parse CPU/memory for requests about to be
            # rejected.  Admission rides a cheap row estimate, trued up
            # to the parsed count below.
            admitted = _estimate_rows(body, is_json)
            if not state.try_admit(admitted):
                state.metrics.overload_rejected()
                self._respond(503, _error_json(RuntimeError(
                    "overloaded: %d rows in flight (budget %d); "
                    "retry later" % (state.inflight_rows,
                                     state.max_inflight_rows))),
                    "application/json", headers=retry_hdr)
                return 503, 0, mlabel, None
            try:
                if is_json:
                    payload = RowsPayload(_parse_json_rows(body))
                    family = ("rows",)
                elif forest.engine == "jax":
                    payload = RowsPayload(_parse_text_rows(body, forest))
                    family = ("rows",)
                else:
                    fmt, sep = _sniff_sep(body)
                    payload = TextPayload(body, fmt, sep)
                    family = ("text", fmt, sep)
                nrows = payload.nrows
                if nrows != admitted:
                    # true up to the real row count (an already-admitted
                    # request keeps its slot even if the estimate ran
                    # low — like the idle-server oversized case)
                    state.release(admitted - nrows)
                    admitted = nrows
                if state.fast_lane(nrows):
                    # low-latency lane: answer on THIS thread, never
                    # queued behind a forming batch
                    lane = "fast"
                    parts = state.fast_predict(forest, payload, mode)
                else:
                    lane = "batch"
                    parts = state.batcher.submit((forest, mode, family),
                                                 payload)
            except BatcherClosed:
                # raced the drain past the flag check above
                self._respond(503, _error_json(
                    RuntimeError("draining")), "application/json",
                    headers=retry_hdr)
                return 503, 0, mlabel, None
            except log.LightGBMError as ex:
                raise BadRequest(str(ex))
            finally:
                state.release(admitted)
            self._respond(200, b"".join(parts))
            return 200, nrows, mlabel, lane

        def _reload(self, url: ParseResult) -> int:
            body = self._body()
            q = parse_qs(url.query)
            # /reload?model=<path> is the fleet's PER-MODEL in-place
            # reload: an ALREADY-REGISTERED entry re-parses + re-warms,
            # the default model stays put (unregistered paths 400).  A
            # body {"model": path} without the query keeps the
            # single-model semantics: swap the default (the operator-
            # initiated way a new path enters the registry over HTTP).
            # Body {"model": path, "default": false} is the deploy
            # agent's challenger PUSH: register + warm WITHOUT
            # promotion, so shadow traffic can hit /predict?model=
            # while the champion stays default.
            in_place = q.get("model", [None])[0]
            path = in_place or state.cfg.input_model
            make_default = not in_place
            register_new = False
            if body.strip():
                try:
                    doc = json.loads(body.decode("utf-8"))
                except (ValueError, UnicodeDecodeError) as ex:
                    raise BadRequest("invalid JSON body: %s" % ex)
                if isinstance(doc, dict) and doc.get("model"):
                    if in_place:
                        raise BadRequest(
                            "give the model either as ?model= or in "
                            "the body, not both")
                    path = str(doc["model"])
                    if "default" in doc and not doc["default"]:
                        make_default = False
                        register_new = True
            if not path:
                raise BadRequest("no model path: configure input_model "
                                 'or POST {"model": "<path>"}')
            try:
                info = state.reload(path, make_default=make_default,
                                    register_new=register_new)
            except Exception as ex:
                # ANY reload failure leaves the old forest serving
                # (the swap happens last inside state.reload); report
                # it structurally — client faults (missing/corrupt
                # model) as 4xx, everything else as 5xx — and count it
                state.metrics.reload_failed()
                code = (400 if isinstance(
                    ex, (OSError, log.LightGBMError, BadRequest,
                         UnknownModelError))
                    else 500)
                log.warning("serve: reload failed (%s: %s); old model "
                            "kept serving" % (type(ex).__name__, ex))
                self._respond(code, _error_json(ex), "application/json")
                return code
            self._respond(200, json.dumps(info).encode(),
                          "application/json")
            return 200

    return Handler


def _qbool(q: Dict[str, List[str]], key: str, default: bool) -> bool:
    if key not in q:
        return default
    return q[key][0].strip().lower() in ("1", "true", "+", "yes")


class _HTTPServer(ThreadingHTTPServer):
    # the stdlib backlog of 5 overflows into client ConnectionResets
    # when closed-loop one-connection-per-request clients pile up while
    # a /reload warm() stalls the accept loop (the multi-client stress
    # test reproduced it); a deeper listen queue absorbs the burst
    request_queue_size = 128

    def __init__(self, addr: Tuple[str, int], handler: type,
                 reuse_port: bool = False):
        self._reuse_port = reuse_port
        super().__init__(addr, handler)

    def server_bind(self) -> None:
        if self._reuse_port:
            # multi-process front-end (serving/frontend.py): N worker
            # processes bind the SAME port and the kernel load-balances
            # accepted connections across them — the flag must be set
            # BEFORE bind, on every socket sharing the port
            self.socket.setsockopt(socket.SOL_SOCKET,
                                   socket.SO_REUSEPORT, 1)
        super().server_bind()


class ServingServer:
    """Constructed server, not yet draining — tests/bench drive this
    directly; the CLI wraps it in serve_forever()."""

    def __init__(self, cfg: Config, forest: Optional[ServingForest] = None,
                 reuse_port: bool = False,
                 worker_index: Optional[int] = None):
        if forest is None:
            if not cfg.input_model:
                log.fatal("Need a model file for serving (input_model)")
            forest = load_forest(cfg.input_model,
                                 num_model_predict=cfg.num_model_predict,
                                 backend=cfg.serve_backend,
                                 matmul=cfg.serve_matmul,
                                 matmul_min_rows=cfg.serve_matmul_min_rows,
                                 device_type=cfg.device_type)
        elif cfg.device_type == "tpu":
            # a forest the caller built is held to the config's device
            # like one loaded here (ServingForest._pick_engine)
            if forest.engine != "jax":
                log.fatal("device_type=tpu but the forest serves from "
                          "the %s engine" % forest.engine)
            from ..utils.device import resolve_device
            resolve_device(cfg.device_type)
        t0 = time.time()
        n_buckets = forest.warm(cfg.serve_max_batch_rows)
        log.info("Warmed %s serving forest (%d trees, %d bucket "
                 "executables) in %.3f s"
                 % (forest.engine, forest.num_models, n_buckets,
                    time.time() - t0))
        self.state = ServingState(cfg, forest, worker_index=worker_index)
        log.info("Serve lane: low-latency %s (<= %d rows synchronous, "
                 "flat table %s)"
                 % (cfg.serve_low_latency, self.state.lane_max_rows,
                    "ready" if forest.flat_ready else "lazy"))
        # fleet preload: every serve_models path registers; the ones
        # that fit the warm pool parse + warm NOW so the first
        # /predict?model= request pays no cold start.  Preloads warm
        # EAGERLY (startup is the time to pay bucket compiles) — only
        # on-demand cold hits take the fleet's lazy warm.
        for path in self.state.fleet.registered_paths():
            if path != forest.source \
                    and len(self.state.fleet.warm_models()) \
                    < cfg.serve_fleet_max_models:
                self.state.fleet.get(path).warm(cfg.serve_max_batch_rows)
        self.httpd = _HTTPServer((cfg.serve_host, cfg.serve_port),
                                 _make_handler(self.state),
                                 reuse_port=reuse_port)
        self.httpd.daemon_threads = True
        self._lifecycle_lock = threading.Lock()
        self._serve_started = False
        self._closed = False

    @property
    def address(self) -> Tuple[str, int]:
        return self.httpd.server_address[:2]

    @property
    def url(self) -> str:
        host, port = self.address
        return "http://%s:%d" % (host, port)

    def serve_forever(self) -> None:
        with self._lifecycle_lock:
            if self._closed:
                return   # shutdown() won the race: socket already closed
            self._serve_started = True
        self.httpd.serve_forever(poll_interval=0.1)

    def shutdown(self, drain_timeout: float = 30.0) -> None:
        """Graceful drain: stop accepting, finish queued work, then
        wait for the handler threads to WRITE their responses (they are
        daemon threads — exiting while one is mid-write would reset the
        client connection)."""
        # graftlint: disable=GL006 -- single GIL-atomic bool flip with
        # no invariant coupling: a handler that reads stale False just
        # falls into the BatcherClosed race path and still 503s
        self.state.draining = True
        with self._lifecycle_lock:
            self._closed = True
            started = self._serve_started
        if started:
            # safe even if the serve thread set the flag but has not
            # entered the loop yet: BaseServer.serve_forever checks the
            # shutdown request on entry and signals right back
            self.httpd.shutdown()
        # never started (and _closed now blocks it from starting):
        # BaseServer.shutdown() would wait forever on the event only the
        # serve loop sets, so skip straight to closing the socket
        self.httpd.server_close()
        self.state.batcher.shutdown()
        deadline = time.monotonic() + drain_timeout
        while (self.state.metrics.in_flight > 0
               and time.monotonic() < deadline):
            time.sleep(0.01)


def run_until_signal(server: ServingServer) -> None:
    """Run a constructed server until SIGTERM/SIGINT, then drain —
    shared by the single-process CLI entry and every front-end worker
    process (serving/frontend.py)."""
    stop = threading.Event()

    def _on_signal(signum: int, frame: Any) -> None:
        log.info("Signal %d: draining..." % signum)
        stop.set()

    prev: Dict[int, Any] = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(sig, _on_signal)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        stop.wait()
    finally:
        for sig, h in prev.items():
            signal.signal(sig, h)
        server.shutdown()
        t.join(10)
        log.info("Serve drained, exiting")


def serve_forever(cfg: Config) -> None:
    """CLI entry (task=serve, single process): run until SIGTERM/
    SIGINT, then drain."""
    server = ServingServer(cfg)
    host, port = server.address
    log.info("Serving %s on http://%s:%d (max_batch_rows=%d, "
             "batch_timeout_ms=%g)"
             % (server.state.forest.source, host, port,
                cfg.serve_max_batch_rows, cfg.serve_batch_timeout_ms))
    run_until_signal(server)
