"""Warm, device-resident forest for online serving.

Parses model text ONCE through the shared `models.tree.parse_model_text`
reader (the same one GBDT.load_model_from_string and the native predict
fast path use, so the three cannot drift), flattens the trees to
contiguous arrays, and answers batch predict calls with no per-request
model work:

  - JAX engine (default when the jax stack imports): the stacked
    [T, M] node arrays live on the default device and every batch runs
    one `ops.predict.predict_leaf_stacked` dispatch.  Rows pad up to
    power-of-two buckets (`bucket_rows`) and `warm()` pre-compiles every
    bucket up to `serve_max_batch_rows`, so steady-state requests never
    recompile regardless of batch size.  Batches of
    >= serve_matmul_min_rows rows route through the gather-free matmul
    predictor (`ops.predict.predict_leaf_matmul`, the same kernel and
    pack builder as the batch predict path; its speed on the chip is
    not measured on the current code) with leaf indices identical to
    the descent's by construction (exact rank-encoded compares), so
    the served bytes cannot change with the route.  Score accumulation
    stays on the host in f64 (boosting order), byte-identical to
    `task=predict`.
  - host engine (JAX-free fallback, `serve_backend=native` or jax
    unavailable): raw CSV/TSV request text goes through the fused
    native kernel (`native.predict_chunk` — parse -> descend ->
    transform -> "%g" in one multithreaded pass), and parsed float rows
    (JSON requests) take the vectorized numpy descent with the same
    exact f64 `<=` routing and accumulation order.

Output formatting (`format_rows`) replicates cli.predict's format_block
byte-for-byte: native "%g" bulk formatting when available, Python "%g"
otherwise (identical for finite doubles).
"""

from __future__ import annotations

__jax_free__ = True

import hashlib
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..analysis.contracts import contract
from ..models.tree import Tree, parse_model_text
from ..resilience.faults import faultpoint
from ..utils import log
from .flatforest import FlatForest, compile_flat

MODES = ("normal", "raw", "leaf")

# trees per matmul scan block (the batch predictor's constant,
# models/gbdt.py PREDICT_TREE_BLOCK — the serving pack mirrors it so
# both sides build the same executable shape)
MATMUL_TREE_BLOCK = 8

#: process-wide forest instance counter: makes every ServingForest's
#: identity unique even for byte-identical model text, so batcher keys
#: can never coalesce rows across a reload boundary (next() on a
#: count() iterator is atomic under the GIL)
_INSTANCE_SEQ: Iterator[int] = itertools.count()

# smallest compiled row bucket: tiny interactive requests share one
# executable instead of compiling per row count
BUCKET_FLOOR = 16


def bucket_rows(n: int, floor: int = BUCKET_FLOOR) -> int:
    """Power-of-two row bucket for a batch of n rows (>= floor)."""
    b = floor
    while b < n:
        b <<= 1
    return b


class ServingForest:
    """One loaded model, ready to answer predict batches.

    Immutable after construction + warm(): hot swap builds a NEW
    ServingForest off to the side and swaps the reference (server.py),
    so no locking is needed on the predict path.
    """

    def __init__(self, model_text: str, num_model_predict: int = -1,
                 backend: str = "auto", source: str = "<string>",
                 matmul: str = "auto", matmul_min_rows: int = 1024,
                 device_type: str = ""):
        header, trees = parse_model_text(model_text)
        self.num_class: int = header["num_class"]
        self.label_idx: int = header["label_index"]
        self.max_feature_idx: int = header["max_feature_idx"]
        # prediction-only sigmoid default, like cli.init_predict's GBDT
        # (no binary objective configured -> -1)
        self.sigmoid: float = (header["sigmoid"]
                               if header["sigmoid"] is not None else -1.0)
        # set_num_used_model resolution shared with the predict fast
        # path (models.tree.select_used_trees)
        from ..models.tree import select_used_trees
        self.trees: List[Tree] = select_used_trees(
            trees, self.num_class, num_model_predict)
        self.num_models = len(self.trees)
        self.source = source
        self.loaded_at = time.time()
        # EXPLICIT model identity: content hash + per-process instance
        # number.  Batcher keys compare forests through __eq__/__hash__
        # below, so "same bytes, different load" (a reload mid-flight)
        # can never coalesce into one dispatch, and the sha travels to
        # /healthz + /metrics so probes can tell WHICH model answers.
        self.content_sha: str = hashlib.sha256(
            model_text.encode("utf-8")).hexdigest()
        self.identity: Tuple[str, int] = (self.content_sha,
                                          next(_INSTANCE_SEQ))

        self._engine = self._pick_engine(backend, device_type)
        self._degraded = False          # circuit breaker pinned us to host
        self._lock = threading.Lock()   # guards lazy pack builds only
        self._jax_pack: Optional[Dict[str, Any]] = None
        self._native_spec: Optional[Any] = None
        self._native_spec_tried = False
        self._host_pack: Optional[Dict[str, Any]] = None
        self._flat: Optional[FlatForest] = None
        # device matmul routing (serve_matmul / serve_matmul_min_rows):
        # batches of >= matmul_min_rows rows dispatch through the
        # gather-free matmul predictor instead of the stacked descent
        self._matmul_mode = matmul
        self.matmul_min_rows = int(matmul_min_rows)
        self._matmul_disabled = False   # breaker stage 1 pins descent
        self._mm_pack: Optional[Tuple[Any, ...]] = None
        self._mm_tried = False
        if self._engine == "jax":
            self._build_jax_pack()

    # identity semantics: two forests are "the same batch key" iff they
    # are the same LOAD of the same bytes — reloads and re-warms always
    # differ (the instance counter), byte-different models always
    # differ (the sha)
    def __eq__(self, other: object) -> bool:
        if not isinstance(other, ServingForest):
            return NotImplemented
        return self.identity == other.identity

    def __hash__(self) -> int:
        return hash(self.identity)

    # -- engine selection ----------------------------------------------
    @staticmethod
    def _pick_engine(backend: str, device_type: str = "") -> str:
        """native -> host engine.  jax / auto -> the device engine,
        on the platform `device_type` names (utils/device.py: fatal
        when it is another, so a forest built outside cli.run cannot
        answer device_type=tpu from the CPU backend).  Only auto with a
        jax that does not import selects the host engine, and says so;
        under device_type=tpu that import error is raised instead."""
        if backend == "native":
            return "host"
        try:
            import jax  # noqa: F401
        except ImportError as ex:
            if backend == "jax" or device_type == "tpu":
                raise
            log.warning("serve_backend=auto: jax does not import (%s); "
                        "serving from the host engine" % ex)
            return "host"
        from ..utils.device import resolve_device
        resolve_device(device_type)
        return "jax"

    @property
    def engine(self) -> str:
        return self._engine

    @property
    def degraded(self) -> bool:
        return self._degraded

    @property
    def matmul_disabled(self) -> bool:
        return self._matmul_disabled

    def degrade(self) -> None:
        """Circuit breaker (final stage): pin this forest to the
        JAX-free host engine after repeated device-dispatch failures.
        One-way until /reload builds a fresh forest; the host packs
        warm immediately so the next request needs no lazy build."""
        with self._lock:
            if self._engine != "jax":
                return
            self._engine = "host"
            self._degraded = True
        self._build_host_pack()
        self._native_forest()

    def disable_matmul(self) -> None:
        """Circuit breaker stage 1: matmul -> descent.  The device
        engine keeps serving through the stacked-descent route (whose
        buckets warm() already compiled); a further failure streak
        takes the degrade() stage down to the host engine."""
        with self._lock:
            self._matmul_disabled = True

    # -- packed representations ----------------------------------------
    def _flat_arrays(self) -> Tuple[np.ndarray, np.ndarray,
                                    np.ndarray, np.ndarray, np.ndarray]:
        """[T, M] padded node arrays + [T, L] leaf values (the
        GBDT._stacked_trees layout, rebuilt here without a jax import)."""
        trees = self.trees
        t = len(trees)
        max_l = max((tr.num_leaves for tr in trees), default=1)
        m = max(1, max_l - 1)
        sf = np.zeros((t, m), dtype=np.int32)
        thr = np.zeros((t, m), dtype=np.float64)
        lc = np.full((t, m), -1, dtype=np.int32)
        rc = np.full((t, m), -1, dtype=np.int32)
        lv = np.zeros((t, max_l), dtype=np.float64)
        for i, tr in enumerate(trees):
            ni = tr.num_leaves - 1
            if ni > 0:
                sf[i, :ni] = tr.split_feature_real[:ni]
                thr[i, :ni] = tr.threshold[:ni]
                lc[i, :ni] = tr.left_child[:ni]
                rc[i, :ni] = tr.right_child[:ni]
            # ni == 0 keeps lc[i, 0] == -1 == ~0: every row -> leaf 0
            lv[i, :tr.num_leaves] = tr.leaf_value[:tr.num_leaves]
        return sf, thr, lc, rc, lv

    def _build_jax_pack(self) -> Dict[str, Any]:
        if self._jax_pack is not None:
            return self._jax_pack
        with self._lock:
            if self._jax_pack is None:
                import jax.numpy as jnp
                from ..ops.predict import split_hi_lo
                sf, thr, lc, rc, lv = self._flat_arrays()
                th, tl = split_hi_lo(thr)
                dev = tuple(jnp.asarray(a)
                            for a in (sf, th, tl, lc, rc))
                self._jax_pack = {"dev": dev, "lv": lv}
        return self._jax_pack

    def _build_mm_pack(self) -> Optional[Tuple[Any, ...]]:
        """(tables, device arrays) for the gather-free matmul predictor,
        or None when the pack declines (wide features / uint16 code
        overflow — ops/predict.matmul_host_arrays, the SAME builder the
        batch predictor uses, so the two cannot drift)."""
        if not self._mm_tried:
            with self._lock:
                if not self._mm_tried:
                    import jax.numpy as jnp
                    from ..ops.predict import matmul_host_arrays
                    sf, thr, lc, rc, _ = self._flat_arrays()
                    from ..ops.predict import split_hi_lo
                    th, tl = split_hi_lo(thr)
                    max_l = max((tr.num_leaves for tr in self.trees),
                                default=1)
                    m = max(1, max_l - 1)
                    host = matmul_host_arrays(
                        self.trees, sf, th, tl, lc, rc, max_l, m,
                        self.max_feature_idx + 1, MATMUL_TREE_BLOCK)
                    if host is not None:
                        tables, sel, thr_code, pos, neg, depth = host
                        self._mm_pack = (tables, tuple(
                            jnp.asarray(a)
                            for a in (sel, thr_code, pos, neg, depth)))
                    self._mm_tried = True
        return self._mm_pack

    def matmul_enabled(self) -> bool:
        """Whether the matmul route is in play for this forest at all
        (engine, config mode, breaker stage 1)."""
        if self._engine != "jax" or self._matmul_disabled:
            return False
        if self._matmul_mode == "off":
            return False
        if self._matmul_mode == "on":
            return True
        # auto: accelerators only — on CPU the descent's gathers are
        # cheap and the O(C * T * M) compare work of the matmul form
        # loses (the batch predictor draws the same line, gbdt.py
        # _predict_leaves)
        import jax
        return jax.default_backend() != "cpu"

    def matmul_routed(self, n: int) -> bool:
        """Deterministic route decision for an n-row device batch — the
        breaker asks it post-failure to learn which route failed."""
        return (n >= self.matmul_min_rows and self.matmul_enabled()
                and self._build_mm_pack() is not None)

    def matmul_live(self) -> bool:
        """True when the matmul route is actually dispatching batches
        (enabled AND the pack built successfully) — the breaker's
        stage-1 question: is there a matmul stage left to turn off?"""
        return self.matmul_enabled() and self._mm_pack is not None

    @contract.jax_free
    def _build_flat(self) -> FlatForest:
        """Flat quantized node table for the low-latency lane
        (serving/flatforest.py): rank-encoded thresholds from the SAME
        tables the matmul pack builds, vectorized host descent, leaf
        indices identical to every other route by construction.

        @contract.jax_free: the fast lane serves from this table inside
        backend=native worker processes — graftcheck GC002 verifies the
        build can never pull jax in."""
        if self._flat is None:
            with self._lock:
                if self._flat is None:
                    sf, thr, lc, rc, _ = self._flat_arrays()
                    self._flat = compile_flat(self.trees, sf, thr, lc,
                                              rc, self.max_feature_idx + 1)
        return self._flat

    @property
    def flat_ready(self) -> bool:
        """Whether the fast lane can serve without a lazy build."""
        return self._flat is not None

    @contract.jax_free
    def _build_host_pack(self) -> Dict[str, Any]:
        if self._host_pack is not None:
            return self._host_pack
        with self._lock:
            if self._host_pack is None:
                _, _, _, _, lv = self._flat_arrays()
                self._host_pack = {"lv": lv}
        return self._host_pack

    @contract.jax_free
    def _native_forest(self) -> Optional[Any]:
        """native.ForestSpec for the fused text kernel, or None.

        @contract.jax_free: this is the serving fallback engine —
        graftcheck GC002 verifies the native spec build cannot pull
        jax into a backend=native server process."""
        if not self._native_spec_tried:
            with self._lock:
                if not self._native_spec_tried:
                    from .. import native
                    if self.trees and native.get_lib() is not None:
                        self._native_spec = native.ForestSpec(
                            self.trees, self.num_class, self.sigmoid)
                    self._native_spec_tried = True
        return self._native_spec

    # -- prediction ------------------------------------------------------
    def fit_width(self, x: np.ndarray) -> np.ndarray:
        """Pad/truncate to the model's feature width: absent trailing
        features read 0.0, extra columns drop (predictor.hpp's
        p.first < num_features rule)."""
        x = np.asarray(x, dtype=np.float64)
        if x.ndim != 2:
            raise ValueError("feature rows must be 2-D, got %r"
                             % (x.shape,))
        want = self.max_feature_idx + 1
        if x.shape[1] < want:
            x = np.pad(x, ((0, 0), (0, want - x.shape[1])))
        elif x.shape[1] > want:
            x = x[:, :want]
        return x

    def _leaves(self, x: np.ndarray, engine: Optional[str] = None,
                route: Optional[str] = None) -> np.ndarray:
        """[N, F] f64 -> [N, T] leaf indices, one dispatch (JAX engine)
        or the vectorized numpy descent (host engine) — identical f64
        `value <= threshold` routing either way.  `engine` overrides
        the forest's engine for THIS call (the circuit breaker answers
        a failed device dispatch on the host path); `route` pins the
        device kernel ('matmul' | 'descent') for warm-up and the
        breaker's stage-1 fallback — by default batches of
        >= matmul_min_rows rows take the gather-free matmul predictor
        (exact rank-encoded compares: leaf indices are IDENTICAL to the
        descent's, tests pin the served bytes)."""
        n = x.shape[0]
        if engine == "flat":
            # low-latency lane: vectorized host descent over the flat
            # quantized node table — jax-free, no device dispatch, leaf
            # indices identical to both device routes by construction
            return self._build_flat().leaves(x)
        if (engine or self._engine) == "jax":
            # the device dispatch is a real failure seam (OOM, backend
            # death): chaos schedules fail it here
            faultpoint("serve.dispatch")
            import jax.numpy as jnp
            from ..ops.predict import (predict_leaf_matmul,
                                       predict_leaf_stacked, rank_encode,
                                       split_hi_lo)
            use_mm = (self.matmul_routed(n) if route is None
                      else route == "matmul")
            b = bucket_rows(n)
            if b > n:
                x = np.pad(x, ((0, b - n), (0, 0)))
            xh, xl = split_hi_lo(x)
            if use_mm:
                mm = self._build_mm_pack()
                assert mm is not None   # matmul_routed/warm checked
                tables, mm_dev = mm
                code = rank_encode(xh, xl, tables)
                leaves = predict_leaf_matmul(
                    *mm_dev, jnp.asarray(code),
                    tree_block=MATMUL_TREE_BLOCK)
                # dummy block-padding trees slice off; int64 matches the
                # host descent's dtype so formatted bytes cannot differ
                return np.asarray(leaves)[:n, :self.num_models] \
                    .astype(np.int64)
            pack = self._build_jax_pack()
            leaves = predict_leaf_stacked(*pack["dev"], jnp.asarray(xh),
                                          jnp.asarray(xl))
            return np.asarray(leaves)[:n]
        out = np.empty((n, self.num_models), dtype=np.int64)
        for i, tr in enumerate(self.trees):
            out[:, i] = tr.predict_leaf_index(x)
        return out

    def predict(self, x: np.ndarray, mode: str,
                engine: Optional[str] = None,
                route: Optional[str] = None) -> np.ndarray:
        """Batch predict on parsed rows.  mode 'leaf' -> [N, T] int;
        'raw'/'normal' -> [K, N] f64 (normal applies sigmoid/softmax,
        the exact GBDT.predict expressions).  `engine` forces one
        engine for this call (circuit-breaker fallback); `route` pins
        the device kernel (matmul | descent).  Bytes are identical on
        every engine and route (tests pin the parity)."""
        if mode not in MODES:
            raise ValueError("unknown predict mode %r" % mode)
        eng = engine or self._engine
        x = self.fit_width(x)
        n = x.shape[0]
        k = self.num_class
        t = self.num_models
        if mode == "leaf":
            if n == 0 or t == 0:
                return np.zeros((n, t), dtype=np.int64)
            return self._leaves(x, eng, route)
        if n == 0 or t == 0:
            raw = np.zeros((k, n), dtype=np.float64)
        else:
            leaves = self._leaves(x, eng, route)
            lv = (self._build_jax_pack() if eng == "jax"
                  else self._build_host_pack())["lv"]
            raw = np.zeros((k, n), dtype=np.float64)
            # per-tree f64 accumulation in boosting order, exactly the
            # reference predictor's += tree->Predict (predictor.hpp:35-70)
            for i in range(t):
                raw[i % k] += lv[i, leaves[:, i]]
        if mode == "raw":
            return raw
        if self.sigmoid > 0:
            return 1.0 / (1.0 + np.exp(-2.0 * self.sigmoid * raw))
        if k > 1:
            e = np.exp(raw - raw.max(axis=0, keepdims=True))
            return e / e.sum(axis=0, keepdims=True)
        return raw

    def predict_text(self, text: bytes, fmt: str, sep: str,
                     mode: str) -> Optional[Tuple[bytes, int]]:
        """Fused native pass over raw request lines (header already
        stripped): (formatted bytes, rows), or None when the native
        kernel is unavailable/refuses — callers parse + predict()
        instead.  This is the JAX-free fallback the host engine serves
        CSV/TSV requests through (predict_fast's warm loop, request-
        sized)."""
        spec = self._native_forest()
        if spec is None:
            return None
        from .. import native
        mode_i = {"normal": 0, "raw": 1, "leaf": 2}[mode]
        return native.predict_chunk(text, fmt, sep, self.label_idx,
                                    self.max_feature_idx + 1, spec, mode_i)

    def format_rows(self, res: np.ndarray, mode: str) -> bytes:
        """Result array -> response bytes through the SAME formatter as
        cli.predict's blocks (predict_fast.format_pred_rows), so served
        bytes cannot drift from task=predict's."""
        from ..predict_fast import format_pred_rows
        return format_pred_rows(res, mode == "leaf")

    # -- warm-up ---------------------------------------------------------
    def warm(self, max_batch_rows: int, lazy: bool = False) -> int:
        """Pre-compile every power-of-two row bucket up to
        max_batch_rows (JAX engine; the host engine just builds its
        packs).  Buckets at or above the matmul threshold compile BOTH
        routes — the matmul executable that serves them and the descent
        executable the breaker's stage-1 fallback answers on — so
        steady state stays at zero recompiles even mid-degrade.
        Returns the number of compiled (bucket, route) executables so
        callers can log/measure.

        lazy=True is the fleet's cold-load mode at thousand-model
        scale: only the host-side state builds NOW — the flat table
        (the fast lane serves immediately) and the host packs — while
        device bucket executables compile on the first routed batch
        (the jit cache keys on shapes, so same-shaped fleet models hit
        already-compiled executables anyway)."""
        # the flat table always builds: the low-latency lane serves
        # from it regardless of engine, and it doubles as the host
        # fallback's O(level) descent
        self._build_flat()
        if self._engine != "jax":
            self._build_host_pack()
            self._native_forest()
            return 0
        if lazy:
            self._build_host_pack()
            return 0
        n_buckets = 0
        b = BUCKET_FLOOR
        while True:
            rows = min(b, max_batch_rows)
            dummy = np.zeros((rows, self.max_feature_idx + 1))
            self.predict(dummy, "raw")
            n_buckets += 1
            if self.matmul_routed(rows):
                # the auto route above took matmul: pre-compile the
                # descent executable for the same bucket too
                self.predict(dummy, "raw", route="descent")
                n_buckets += 1
            if b >= max_batch_rows:
                break
            b <<= 1
        return n_buckets

    # -- introspection ---------------------------------------------------
    def info(self) -> Dict[str, Any]:
        return {
            "source": self.source,
            "sha": self.content_sha,
            "engine": self._engine,
            "degraded": self._degraded,
            # pack-build is lazy: before the first routed batch this
            # reports the config/breaker state; once tried, whether the
            # pack actually accepted the model
            "matmul": (self.matmul_enabled()
                       and (self._mm_pack is not None
                            or not self._mm_tried)),
            "matmul_min_rows": self.matmul_min_rows,
            # fast-lane state: whether the flat table is resident, and
            # its size (the number fleet capacity planning sums)
            "flat": self._flat is not None,
            "flat_bytes": (self._flat.nbytes()
                           if self._flat is not None else 0),
            "num_models": self.num_models,
            "num_class": self.num_class,
            "max_feature_idx": self.max_feature_idx,
            "loaded_at": self.loaded_at,
        }


def load_forest(path: str, num_model_predict: int = -1,
                backend: str = "auto", matmul: str = "auto",
                matmul_min_rows: int = 1024,
                device_type: str = "") -> ServingForest:
    """Read + parse + pack a model file (no warm-up; callers warm)."""
    with open(path) as f:
        text = f.read()
    if not text.strip():
        log.fatal("Model file %s is empty" % path)
    return ServingForest(text, num_model_predict=num_model_predict,
                         backend=backend, source=path, matmul=matmul,
                         matmul_min_rows=matmul_min_rows,
                         device_type=device_type)
