"""Multi-model fleet: N hot models behind an LRU + age warm pool.

One server process can hold many models warm at once — the per-tenant
shape production serving actually runs: the default model answers
`/predict`, `/predict?model=<path>` routes to any REGISTERED model
(loading + warming it on first use), and the warm pool bounds how many
forests stay resident two ways: LRU capacity (`serve_fleet_max_models`)
and idle age (`serve_fleet_evict_age_s` — a warm model untouched that
long drops at the next pool access).  Registered models past either
bound re-warm on demand; the default model is pinned and never evicted.

Cold loads warm LAZILY (forest.warm(lazy=True)): the flat table and
host packs build immediately — the low-latency lane serves the very
first hit — while device bucket executables compile on the first routed
batch (the jit cache keys on shapes, so same-shaped fleet models reuse
already-compiled executables).  That keeps a cold hit to parse + pack
cost, which is what lets the pool scale toward thousands of per-tenant
models instead of 4.

Batches can never coalesce across models: the batcher keys on the
ServingForest itself, whose __eq__/__hash__ compare the EXPLICIT
identity (content sha, per-process instance number) — a reload
mid-flight yields a new instance, so in-flight rows finish on the old
forest and new rows batch on the new one (tests/test_serving_fleet.py
pins it).

Eviction is GC-safe: forests are immutable after warm(), and in-flight
batches hold their forest through the batch key, so an evicted forest
finishes its dispatches before it is collected.
"""

from __future__ import annotations

__jax_free__ = True

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from ..config import Config
from ..utils import log
from .forest import ServingForest, load_forest


class UnknownModelError(KeyError):
    """/predict?model= named a path that was never registered."""


class ModelFleet:
    """LRU warm pool of ServingForests, keyed by model path.

    The default model (cfg.input_model / the preloaded forest) is
    pinned; extra models register via cfg.serve_models, /reload, or
    register().  All pool mutation happens under `_lock`; the slow
    parse+warm of a miss runs under `_load_lock` OUTSIDE the pool lock,
    so hits keep serving while a cold model warms.
    """

    def __init__(self, cfg: Config, default_forest: ServingForest):
        self.cfg = cfg
        self.max_models = int(cfg.serve_fleet_max_models)
        self.evict_age_s = float(cfg.serve_fleet_evict_age_s)
        self._lock = threading.Lock()        # pool + registry state
        self._load_lock = threading.Lock()   # serializes cold loads
        default_path = default_forest.source
        self._default_path = default_path
        # last pool access per path (monotonic), for age eviction
        self._last_used: Dict[str, float] = {
            default_path: time.monotonic()}
        # path -> warm forest, in LRU order (last = most recent)
        self._pool: "OrderedDict[str, ServingForest]" = OrderedDict()
        self._pool[default_path] = default_forest
        # registered paths (the allowed /predict?model= set); values are
        # unused — an OrderedDict keeps registration order for listings
        self._registered: "OrderedDict[str, bool]" = OrderedDict()
        self._registered[default_path] = True
        for path in (cfg.serve_models or "").split(","):
            path = path.strip()
            if path:
                self._registered[path] = True

    # -- lookup ----------------------------------------------------------
    @property
    def default_path(self) -> str:
        with self._lock:
            return self._default_path

    def default(self) -> ServingForest:
        with self._lock:
            forest = self._pool[self._default_path]
            self._pool.move_to_end(self._default_path)
            self._last_used[self._default_path] = time.monotonic()
            return forest

    def contains(self, forest: ServingForest) -> bool:
        """Is this exact forest instance currently pooled?  (The
        circuit breaker only counts failures of live forests.)"""
        with self._lock:
            return any(f is forest for f in self._pool.values())

    def get(self, path: Optional[str] = None) -> ServingForest:
        """The warm forest for `path` (default model when None).
        Unregistered paths raise UnknownModelError — serving must not
        read arbitrary files off a query parameter."""
        if path is None or path == "":
            return self.default()
        with self._lock:
            if path not in self._registered:
                raise UnknownModelError(path)
            self._evict_stale()
            forest = self._pool.get(path)
            if forest is not None:
                self._pool.move_to_end(path)
                self._last_used[path] = time.monotonic()
                return forest
        return self._load(path)

    # -- mutation --------------------------------------------------------
    def register(self, path: str) -> None:
        """Allow `path` for /predict?model= (no load yet)."""
        with self._lock:
            self._registered[path] = True

    def reload(self, path: str, make_default: bool = False,
               loader: Any = None, register: bool = False) -> ServingForest:
        """Parse + warm a FRESH forest for `path` off to the side, then
        swap it into the pool atomically (in-flight batches keep keying
        on the old instance).  make_default also repoints the default
        model — the single-model /reload semantics.  register=True is
        the deploy agent's challenger PUSH: the path enters the
        registry and warms WITHOUT becoming default (shadow traffic via
        /predict?model= first; promotion is a later make_default call).
        Both are operator-initiated BODY forms over HTTP — the in-place
        query form (make_default=False, register=False) only refreshes
        an ALREADY-registered entry: a typo'd /reload?model= is a 400,
        not a silent allow-list expansion.  Any failure propagates
        BEFORE the swap, so the old forest keeps serving."""
        if not make_default and not register:
            with self._lock:
                if path not in self._registered:
                    raise UnknownModelError(path)
        fresh = (loader or self._load_fresh)(path)
        with self._lock:
            self._registered[path] = True
            self._pool[path] = fresh
            self._pool.move_to_end(path)
            self._last_used[path] = time.monotonic()
            if make_default:
                self._default_path = path
            self._evict_stale()
            self._evict_over_capacity()
        return fresh

    def _load(self, path: str) -> ServingForest:
        """Cold-miss load: serialized so N concurrent first requests
        for one model parse it once."""
        with self._load_lock:
            with self._lock:
                forest = self._pool.get(path)
                if forest is not None:
                    self._pool.move_to_end(path)
                    return forest
            fresh = self._load_fresh(path)
            with self._lock:
                self._pool[path] = fresh
                self._pool.move_to_end(path)
                self._last_used[path] = time.monotonic()
                self._evict_stale()
                self._evict_over_capacity()
            return fresh

    def _load_fresh(self, path: str) -> ServingForest:
        cfg = self.cfg
        forest = load_forest(path,
                             num_model_predict=cfg.num_model_predict,
                             backend=cfg.serve_backend,
                             matmul=cfg.serve_matmul,
                             matmul_min_rows=cfg.serve_matmul_min_rows,
                             device_type=cfg.device_type)
        # lazy warm: flat table + host packs NOW (the fast lane serves
        # the first hit), device buckets on first routed batch — the
        # cold-hit cost stays bounded at thousand-model fleet scale.
        # Operator paths that want eager buckets (startup preload,
        # /reload) call warm() again themselves.
        forest.warm(cfg.serve_max_batch_rows, lazy=True)
        log.info("Fleet: lazily warmed %s (%d trees, sha %s)"
                 % (path, forest.num_models, forest.content_sha[:12]))
        return forest

    def _evict_over_capacity(self) -> None:
        """Called with _lock held: drop least-recently-used non-default
        forests past max_models.  Their model paths STAY registered —
        the next request re-warms them (LRU warm pool, not an allow-list
        change)."""
        while len(self._pool) > self.max_models:
            victim = next((p for p in self._pool
                           if p != self._default_path), None)
            if victim is None:
                return
            evicted = self._pool.pop(victim)
            self._last_used.pop(victim, None)
            log.info("Fleet: evicted %s (sha %s) from the warm pool"
                     % (victim, evicted.content_sha[:12]))

    def _evict_stale(self) -> None:
        """Called with _lock held: age eviction — non-default forests
        idle past serve_fleet_evict_age_s drop from the pool (still
        registered; the next hit lazily re-warms).  At per-tenant scale
        LRU capacity alone keeps dead tenants resident for hours; age
        is the bound that actually frees their node tables."""
        if self.evict_age_s <= 0:
            return
        now = time.monotonic()
        stale = [p for p in self._pool
                 if p != self._default_path
                 and now - self._last_used.get(p, now) > self.evict_age_s]
        for victim in stale:
            evicted = self._pool.pop(victim)
            self._last_used.pop(victim, None)
            log.info("Fleet: evicted %s (sha %s) — idle past %.3gs"
                     % (victim, evicted.content_sha[:12],
                        self.evict_age_s))

    # -- introspection ---------------------------------------------------
    def warm_models(self) -> List[ServingForest]:
        with self._lock:
            return list(self._pool.values())

    def registered_paths(self) -> List[str]:
        with self._lock:
            return list(self._registered)

    def info(self) -> List[Dict[str, Any]]:
        """Per-model listing for /healthz and /metrics: every registered
        model, warm ones with their full forest info."""
        with self._lock:
            default = self._default_path
            entries = [(p, self._pool.get(p)) for p in self._registered]
        out: List[Dict[str, Any]] = []
        for path, forest in entries:
            if forest is None:
                out.append({"source": path, "warm": False,
                            "default": path == default})
            else:
                doc = forest.info()
                doc["warm"] = True
                doc["default"] = path == default
                out.append(doc)
        return out
