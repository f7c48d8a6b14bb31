"""Objective functions: gradients/hessians of the training loss.

Formula-parity ports (float32 math, like the reference's score_t=float):
  - regression L2: reference src/objective/regression_objective.hpp:24-39
  - binary logloss: reference src/objective/binary_objective.hpp:23-86
  - multiclass softmax: reference src/objective/multiclass_objective.hpp:22-73
  - lambdarank NDCG: reference src/objective/rank_objective.hpp:41-192,
    including the 1M-entry sigmoid lookup table (same table, same index
    math) so gradient values match the reference bit-for-bit on identical
    scores.

Elementwise objectives are jitted jnp; lambdarank is vectorized numpy over
padded per-query blocks (scores are pulled to host once per iteration — the
per-query pairwise O(L^2) work is tiny relative to tree growth).
"""

from __future__ import annotations

from typing import Optional

from .utils.compile_cache import enable_compilation_cache

enable_compilation_cache()   # before any jit traces (was a package-import side effect)

import jax
import jax.numpy as jnp
import numpy as np

from . import native
from .analysis.contracts import contract
from .config import Config
from .io.dataset import Metadata
from .utils import log, spans

K_EPSILON = 1e-15
K_MIN_SCORE = -np.inf


class Objective:
    # True when get_gradients is pure jax over captured device arrays and
    # may be traced inside a fused training step (models/gbdt.py)
    jax_traceable = False
    # True when grad_state can follow a row reordering (the ordered-
    # partition mode, models/gbdt.py) via make_row_state_fn.  By default
    # every leaf is per-row on its last axis; objectives whose state
    # carries row INDICES (lambdarank's doc_idx) override
    # make_row_state_fn to remap them instead.
    row_permutable = False
    # True when the data-parallel fused step can shard grad_state along
    # the data axis (models/gbdt.py _make_fused_step_sharded).  Two ways
    # to qualify: every leaf is per-row on its LAST axis (the default
    # sharding; regression/binary/multiclass), or the objective provides
    # its own query-granular layout + sharded state via shard_layout /
    # build_sharded_state (lambdarank's device path: the [Q, Lmax]
    # query-block state shards along Q with shard-local row indices).
    row_shardable = False
    name = "none"
    num_class = 1
    # integer counts of what ONE tree's gradients cost, set when the state
    # is built (lambdarank's pair pass); nothing for elementwise objectives
    _counters: dict = {}

    def init(self, metadata: Metadata, num_data: int) -> None:
        """Build the label-derived state, inside its start-up span."""
        self.metadata = metadata
        self.num_data = num_data
        self.n_pad = num_data
        stats = {"rows": num_data}
        if metadata.query_boundaries is not None:
            stats["queries"] = len(metadata.query_boundaries) - 1
        with spans.startup(spans.STARTUP_OBJECTIVE, **stats):
            self._init_state(metadata, num_data)

    def _init_state(self, metadata: Metadata, num_data: int) -> None:
        """What a subclass builds from the labels (host arrays, their
        upload, its own jits)."""

    def pad_to(self, n_pad: int) -> None:
        """Extend label-derived device arrays to a padded row count so
        gradients can be computed directly on padded/sharded score arrays
        (padded rows produce values that are masked out of histograms by
        bag_mask and harmless in score updates)."""
        self.n_pad = n_pad

    @staticmethod
    def _pad(arr, n_pad, value=0.0):
        if arr is None or arr.shape[-1] >= n_pad:
            return arr
        pad = [(0, 0)] * (arr.ndim - 1) + [(0, n_pad - arr.shape[-1])]
        return jnp.pad(arr, pad, constant_values=value)

    def get_gradients(self, score):
        raise NotImplementedError

    # -- fused-step surface (models/gbdt.py) ---------------------------
    # The fused training step passes label-derived arrays as jit
    # ARGUMENTS (grad_state) to a pure gradient function (make_grad_fn),
    # so the compiled executable carries no embedded label constants and
    # one executable is shared by every booster whose fused_key matches.
    def fused_key(self):
        """Hashable key fully identifying the gradient computation, or
        None when this objective cannot be traced in the fused step."""
        return None

    def grad_state(self):
        """Pytree of device arrays consumed by make_grad_fn's function."""
        raise NotImplementedError

    def make_grad_fn(self):
        """-> pure fn (score, grad_state) -> (grad, hess).  Two
        objectives with equal fused_key must return functions that trace
        identically."""
        raise NotImplementedError

    @contract.traced_pure
    def make_row_state_fn(self):
        """-> pure fn grad_state -> (rows, rebuild): how grad_state
        follows a row permutation.  `rows` lists the leaves that are
        per-row on their LAST axis; the caller moves them (new position
        j holds old row rel[j]) however is cheapest — models/gbdt.py
        _resort_rows moves the narrow ones together in one gather
        — and `rebuild(moved_rows, rel)` returns the permuted
        grad_state.  Traced inside the fused reorder step, so two
        objectives with equal fused_key must return functions that trace
        identically.  Default: every leaf is per-row (regression/binary/
        multiclass) and `rel` is not needed.

        This is also the bag-compaction hook: the in-bag-first
        arrangement (models/gbdt.py _arrange_for_bag) is a stable row
        permutation, so grad_state follows it the same way — objectives
        whose state carries row indices (lambdarank's doc_idx) remap
        them in `rebuild` and need nothing extra for compaction."""
        def row_state(gstate):
            rows, treedef = jax.tree_util.tree_flatten(gstate)
            return rows, lambda moved, rel: jax.tree_util.tree_unflatten(
                treedef, moved)
        return row_state

    def bag_rows_bound(self, bagging_fraction: float) -> int:
        """Deterministic upper bound on the in-bag ROW count of any
        single re-bagging draw at this fraction — the static size of the
        bag-compacted sweep window (models/gbdt.py).  Row-granular
        bagging draws exactly int(fraction * n) rows (gbdt.cpp:109-131),
        so the bound is exact; query-granular bagging (query_boundaries
        present, gbdt.cpp:133-160) draws int(nq * fraction) whole
        queries whose row total varies per draw — bounded by the sum of
        the largest that-many query lengths."""
        qb = getattr(self.metadata, "query_boundaries", None)
        if qb is None:
            return int(bagging_fraction * self.num_data)
        qb = np.asarray(qb, dtype=np.int64)
        qlen = np.sort(qb[1:] - qb[:-1])[::-1]
        bag_query_cnt = int(len(qlen) * bagging_fraction)
        return int(qlen[:bag_query_cnt].sum())

    # -- query-granular sharding surface (tree_learner=data) -----------
    # Objectives whose grad_state is NOT per-row on its last axis (the
    # lambdarank query blocks) implement these two hooks to still run
    # the fused shard_map step: shard_layout returns the row placement
    # (rows of one query stay on one shard), build_sharded_state the
    # matching shard-major gradient state + PartitionSpecs.
    def shard_layout(self, local_shards: int, row_unit: int, mh: bool):
        """RowShardLayout (parallel/mesh.py) for the data-parallel fused
        step, or None when the default contiguous row blocks work (every
        elementwise objective)."""
        return None

    def build_sharded_state(self, layout, sync=None):
        """-> (host_leaves, specs): numpy grad_state blocks laid out
        shard-major for `layout` plus one PartitionSpec per leaf.  Only
        called when shard_layout returned a layout."""
        raise NotImplementedError

    def trace_counters(self) -> dict:
        """What ONE tree's gradients cost, as integer counts;
        models/gbdt.py _flush_pending carries them as stats of its
        lgbm.flush span."""
        return dict(self._counters)

    def convert_output(self, score: np.ndarray) -> np.ndarray:
        """Final transform for human-facing predictions."""
        return score


class RegressionL2(Objective):
    name = "regression"
    jax_traceable = True
    row_permutable = True
    row_shardable = True

    def __init__(self, config: Config):
        pass

    def _init_state(self, metadata: Metadata, num_data: int) -> None:
        self.label = jnp.asarray(metadata.label, dtype=jnp.float32)
        self.weights = (None if metadata.weights is None
                        else jnp.asarray(metadata.weights, dtype=jnp.float32))

    def pad_to(self, n_pad: int) -> None:
        super().pad_to(n_pad)
        self.label = self._pad(self.label, n_pad)
        self.weights = self._pad(self.weights, n_pad)

    def get_gradients(self, score):
        return self.make_grad_fn()(score, self.grad_state())

    def fused_key(self):
        return ("regression", self.weights is not None)

    def grad_state(self):
        return (self.label, self.weights)

    @staticmethod
    @contract.traced_pure
    def make_grad_fn():
        def grad_fn(score, state):
            label, weights = state
            score = score.astype(jnp.float32)
            grad = score - label
            hess = jnp.ones_like(grad)
            if weights is not None:
                grad = grad * weights
                hess = weights
            return grad, hess
        return grad_fn


class BinaryLogloss(Objective):
    name = "binary"
    jax_traceable = True
    row_permutable = True
    row_shardable = True

    def __init__(self, config: Config):
        self.sigmoid = np.float32(config.sigmoid)
        self.is_unbalance = config.is_unbalance
        if self.sigmoid <= 0:
            log.fatal("Sigmoid parameter %f should be greater than zero"
                      % self.sigmoid)

    def _init_state(self, metadata: Metadata, num_data: int) -> None:
        labels01 = metadata.label.astype(np.int32)
        cnt_pos = int((labels01 == 1).sum())
        cnt_neg = num_data - cnt_pos
        log.info("Number of postive: %d, number of negative: %d"
                 % (cnt_pos, cnt_neg))
        if cnt_pos == 0 or cnt_neg == 0:
            log.fatal("Training data only contains one class")
        w_pos, w_neg = 1.0, 1.0
        if self.is_unbalance:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        sign = np.where(labels01 == 1, 1.0, -1.0).astype(np.float32)
        lw = np.where(labels01 == 1, w_pos, w_neg).astype(np.float32)
        if metadata.weights is not None:
            lw = lw * metadata.weights.astype(np.float32)
        self.sign = jnp.asarray(sign)
        self.label_weight = jnp.asarray(lw)

    def pad_to(self, n_pad: int) -> None:
        super().pad_to(n_pad)
        # sign 0 + weight 0 -> zero grad/hess for padded rows
        self.sign = self._pad(self.sign, n_pad)
        self.label_weight = self._pad(self.label_weight, n_pad)

    def get_gradients(self, score):
        return self.make_grad_fn()(score, self.grad_state())

    def fused_key(self):
        return ("binary", float(self.sigmoid))

    def grad_state(self):
        return (self.sign, self.label_weight)

    @contract.traced_pure
    def make_grad_fn(self):
        sig = jnp.float32(self.sigmoid)

        def grad_fn(score, state):
            sign, label_weight = state
            score = score.astype(jnp.float32)
            response = (-2.0 * sign * sig
                        / (1.0 + jnp.exp(2.0 * sign * sig * score)))
            abs_r = jnp.abs(response)
            grad = response * label_weight
            hess = abs_r * (2.0 * sig - abs_r) * label_weight
            return grad, hess
        return grad_fn

    def convert_output(self, score: np.ndarray) -> np.ndarray:
        return 1.0 / (1.0 + np.exp(-2.0 * float(self.sigmoid) * score))


class MulticlassSoftmax(Objective):
    name = "multiclass"
    # [K, N] gradients feed the MULTICLASS fused step
    # (gbdt._make_fused_step_multi): one dispatch grows all K
    # per-iteration trees via a class-wise lax.scan
    jax_traceable = True
    # the label row [N] / weights [N] both permute on their last axis, so
    # the shared-joint-order multiclass reorder may carry them
    row_permutable = True
    row_shardable = True

    def __init__(self, config: Config):
        self.num_class = config.num_class

    def _label_dtype(self):
        """The narrowest unsigned type that holds every class and the
        padded rows' mark, num_class: the row rides a re-sort's one
        gather of words as a single word row (models/gbdt.py _word_rows),
        where a [K, N] float one-hot took a gather of its own."""
        for dt in (np.uint8, np.uint16):
            if self.num_class <= np.iinfo(dt).max:
                return dt
        return np.int32

    def _init_state(self, metadata: Metadata, num_data: int) -> None:
        li = metadata.label.astype(np.int32)
        if li.min() < 0 or li.max() >= self.num_class:
            log.fatal("Label must be in [0, %d)" % self.num_class)
        self.label = jnp.asarray(li.astype(self._label_dtype()))   # [N]
        self.weights = (None if metadata.weights is None
                        else jnp.asarray(metadata.weights, dtype=jnp.float32))

    def pad_to(self, n_pad: int) -> None:
        super().pad_to(n_pad)
        # a padded row is of no class: its one-hot is all zeros
        self.label = self._pad(self.label, n_pad, value=self.num_class)
        self.weights = self._pad(self.weights, n_pad)

    def get_gradients(self, score):
        """score [K, N] -> grad/hess [K, N] (see make_grad_fn)."""
        return self.make_grad_fn()(score, self.grad_state())

    def fused_key(self):
        return ("multiclass", self.num_class, self.weights is not None)

    def grad_state(self):
        return (self.label, self.weights)

    @staticmethod
    @contract.traced_pure
    def make_grad_fn():
        def grad_fn(score, state):
            """score [K, N] -> grad/hess [K, N].

            The softmax itself runs in float64 with the result cast to
            float32, reproducing the reference's double-precision
            Common::Softmax rec[] with score_t p = (float)rec[k]
            (multiclass_objective.hpp:35-53, common.h:353-367) — under
            default x64-disabled JAX the cast is a no-op and everything
            stays f32.  The one-hot is built here from the label row."""
            label, weights = state
            score = score.astype(jnp.float32)
            onehot = (label[None, :].astype(jnp.int32) == jnp.arange(
                score.shape[0], dtype=jnp.int32)[:, None]).astype(jnp.float32)
            # graftlint: disable=GL003 -- reference parity REQUIRES the
            # f64 softmax (double rec[] in common.h:353-367); with x64
            # off the astype is a no-op and the math stays f32
            p = jax.nn.softmax(score.astype(jnp.float64), axis=0) \
                .astype(jnp.float32)
            grad = p - onehot
            hess = 2.0 * p * (1.0 - p)
            if weights is not None:
                grad = grad * weights[None, :]
                hess = hess * weights[None, :]
            return grad, hess
        return grad_fn

    def convert_output(self, score: np.ndarray) -> np.ndarray:
        e = np.exp(score - score.max(axis=0, keepdims=True))
        return e / e.sum(axis=0, keepdims=True)


_SIGMOID_BINS = 1024 * 1024


class LambdarankNDCG(Objective):
    """LambdaRank with NDCG deltas (reference rank_objective.hpp:41-192).

    Two gradient paths, selected by ``rank_impl``:

    - ``device`` (default): the pairwise per-query computation expressed
      as jnp over padded ``[Q, Lmax]`` query blocks — scores never leave
      the device, the objective traces into the fused training step, and
      the O(L^2) pair tensors are bounded by scanning fixed-size query
      blocks.  Tie order under equal scores follows a STABLE descending
      sort (documented divergence from the reference's non-stable
      std::sort tie permutation; PARITY.md).
    - ``native``: the bit-parity C++ kernel (native/ingest.cpp) that
      reproduces the reference's libstdc++ sort permutation and
      sequential fp32 pair accumulation digit-for-digit — kept as the
      golden-parity oracle, with a vectorized numpy fallback.
    """

    name = "lambdarank"

    def __init__(self, config: Config):
        self.impl = getattr(config, "rank_impl", "device")
        self.jax_traceable = self.impl == "device"
        self.sigmoid = np.float32(config.sigmoid)
        if self.sigmoid <= 0:
            log.fatal("Sigmoid param %f should be greater than zero"
                      % self.sigmoid)
        self.label_gain = np.asarray(config.label_gain or default_label_gain(),
                                     dtype=np.float32)
        self.optimize_pos_at = config.max_position
        # discount table (reference src/metric/dcg_calculator.cpp:27-30)
        self.discount = (1.0 / np.log2(2.0 + np.arange(10000))).astype(np.float32)
        # sigmoid lookup table (reference rank_objective.hpp:175-189)
        self.min_in = np.float32(-50.0) / self.sigmoid / np.float32(2.0)
        self.max_in = -self.min_in
        self.idx_factor = np.float32(_SIGMOID_BINS / (self.max_in - self.min_in))
        ts = (np.arange(_SIGMOID_BINS, dtype=np.float32) / self.idx_factor
              + self.min_in)
        self.sigmoid_table = (
            np.float32(2.0) / (np.float32(1.0)
                               + np.exp(np.float32(2.0) * ts * self.sigmoid)))

    def _init_state(self, metadata: Metadata, num_data: int) -> None:
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        self.qb = metadata.query_boundaries
        # reference src/io/metadata.cpp CheckOrPartition: an undercounting
        # .query sidecar must fatal, not silently hand uncovered rows
        # query-0's gradients via the row_slot default of 0
        if int(self.qb[-1]) != num_data:
            log.fatal("Sum of query counts is not same with #data")
        label = metadata.label
        check_rank_label(label, len(self.label_gain))
        nq = len(self.qb) - 1
        inv = np.zeros(nq, dtype=np.float32)
        for q in range(nq):
            lab = label[self.qb[q]:self.qb[q + 1]]
            m = max_dcg_at_k(self.optimize_pos_at, lab, self.label_gain,
                             self.discount)
            inv[q] = 1.0 / m if m > 0 else m
        self.inverse_max_dcgs = inv
        self.weights = metadata.weights
        if self.impl == "device":
            # the [1, Lmax, Lmax] pair tensors (x ~6 f32 temporaries) grow
            # unbounded in Lmax even at q_block=1; a single 100k-doc query
            # would need tens of GB of HBM.  Past ~16k docs/query the
            # reference-order host path is the right tool.
            qb = np.asarray(self.qb, dtype=np.int64)
            lmax = int((qb[1:] - qb[:-1]).max()) if len(qb) > 1 else 1
            if lmax * lmax * 4 * 6 > (1 << 32):   # >4 GB of pair temps
                log.warning(
                    "Longest query has %d docs; pair tensors would not fit "
                    "in HBM. Falling back to rank_impl=native." % lmax)
                self.impl = "native"
                self.jax_traceable = False
        if self.impl == "device":
            self._build_device_state()
        # the device path's per-doc outputs map back to rows through the
        # per-row row_slot array (every other state leaf is row-POSITION
        # free), so the ordered-partition mode may permute rows: row_slot
        # rides along and doc_idx remaps through the inverse permutation
        # (make_row_state_fn)
        self.row_permutable = self.impl == "device"
        # ... and the data-parallel fused step may shard it: rows shard
        # query-granularly (shard_layout below), each shard's query
        # blocks carry SHARD-LOCAL doc indices, and the same grad_fn /
        # row_state fn run unchanged per shard inside shard_map
        self.row_shardable = self.impl == "device"

    # -- device path ---------------------------------------------------
    def _build_device_state(self) -> None:
        """Pack queries into padded [nb, QB, Lmax] blocks for the jnp
        gradient path.  QB bounds the [QB, Lmax, Lmax] pair tensors that
        dominate memory (scanned block-by-block), so HBM use is
        ~O(QB * Lmax^2) regardless of query count."""
        qb = np.asarray(self.qb, dtype=np.int64)
        nq = len(qb) - 1
        qlen = (qb[1:] - qb[:-1]).astype(np.int64)
        lmax = max(1, int(qlen.max()) if nq else 1)
        # ~16M pair elements per scanned block (~64 MB of f32 temps)
        q_block = int(min(max(1, (1 << 24) // (lmax * lmax)), max(nq, 1)))
        nb = max(1, -(-nq // q_block))
        nq_pad = nb * q_block
        label = np.asarray(self.metadata.label)

        doc_idx = np.zeros((nq_pad, lmax), dtype=np.int32)
        lab = np.full((nq_pad, lmax), -1, dtype=np.int32)
        gain = np.zeros((nq_pad, lmax), dtype=np.float32)
        wts = np.ones((nq_pad, lmax), dtype=np.float32)
        inv = np.zeros(nq_pad, dtype=np.float32)
        inv[:nq] = self.inverse_max_dcgs
        ar = np.arange(lmax, dtype=np.int64)
        for q in range(nq):
            a, ln = int(qb[q]), int(qlen[q])
            idx = a + np.minimum(ar, max(ln - 1, 0))
            doc_idx[q] = idx
            lab[q, :ln] = label[a:a + ln].astype(np.int32)
            gain[q, :ln] = self.label_gain[lab[q, :ln]]
            if self.weights is not None:
                wts[q, :ln] = self.weights[a:a + ln]

        # row -> padded-slot map: every real row occupies exactly one
        # cell of the [nb*QB, Lmax] layout, so the per-doc outputs come
        # back via ONE gather instead of a scatter-add (TPU scatters
        # serialize; gathers of [N] from [Q*L] are cheap).  Padded rows
        # (pad_to) point at the DEAD slot — one extra zero cell appended
        # to the flat output in grad_fn — so the mapping carries no
        # positional assumption and survives row permutations.
        self._dead_slot = nq_pad * lmax
        row_slot = np.zeros(self.num_data, dtype=np.int32)
        for q in range(nq):
            a, ln = int(qb[q]), int(qlen[q])
            row_slot[a:a + ln] = q * lmax + np.arange(ln)

        self._counters = _pair_counters(qlen, nb * q_block, lmax)
        shp = (nb, q_block)
        self._dev_state = (
            jnp.asarray(doc_idx.reshape(shp + (lmax,))),
            jnp.asarray(lab.reshape(shp + (lmax,))),
            jnp.asarray(gain.reshape(shp + (lmax,))),
            jnp.asarray(inv.reshape(shp)),
            jnp.asarray(wts.reshape(shp + (lmax,))),
            jnp.asarray(row_slot),
            jnp.asarray(self.discount),
        )
        self._dev_fn = jax.jit(self.make_grad_fn())

    def pad_to(self, n_pad: int) -> None:
        super().pad_to(n_pad)
        if self.impl != "device":
            return
        (di, lab, gain, inv, wts, row_slot, disc) = self._dev_state
        if row_slot.shape[0] < n_pad:
            dead = jnp.full((n_pad - row_slot.shape[0],), self._dead_slot,
                            dtype=jnp.int32)
            row_slot = jnp.concatenate([row_slot, dead])
            self._dev_state = (di, lab, gain, inv, wts, row_slot, disc)

    def fused_key(self):
        if self.impl != "device":
            return None
        return ("lambdarank", float(self.sigmoid))

    def grad_state(self):
        return self._dev_state

    @contract.traced_pure
    def make_row_state_fn(self):
        """Row permutation support (ordered-partition mode): row_slot is
        per-row and rides the permutation; doc_idx holds row POSITIONS
        into the score vector, so it remaps through the inverse
        permutation.  Everything else (labels/gains/weights/inv_max_dcg/
        discount) is query-block state, independent of row order."""
        def row_state(gstate):
            di, lab, gain, inv, wts, row_slot, disc = gstate

            def rebuild(moved, rel):
                inv_rel = jnp.argsort(rel).astype(jnp.int32)
                return (inv_rel[di], lab, gain, inv, wts, moved[0], disc)
            return [row_slot], rebuild
        return row_state

    # -- query-granular sharding (tree_learner=data fused step) --------
    def shard_layout(self, local_shards: int, row_unit: int, mh: bool):
        """Rows shard on query boundaries: shard s's contiguous device
        block holds whole queries [bounds[s], bounds[s+1]) padded to a
        common capacity, the invariant that lets each shard compute its
        queries' pairwise lambdas from its OWN score block (reference
        rank training under data parallelism is likewise query-local —
        only histograms cross machines,
        data_parallel_tree_learner.cpp:124-187)."""
        if self.impl != "device":
            return None
        from .parallel.mesh import query_shard_layout
        sync = None
        if mh:
            from .parallel.dist import sync_max_ints
            sync = sync_max_ints
        return query_shard_layout(self.qb, local_shards, row_unit, sync)

    def build_sharded_state(self, layout, sync=None):
        """Shard-major [S*nb, QB, Lmax] query-block state for the fused
        shard_map step: the serial _build_device_state layout rebuilt
        per shard with SHARD-LOCAL doc indices (row positions inside the
        shard's own score block) and a per-shard row_slot / dead slot.
        Every shard gets identically-shaped blocks (SPMD); multi-host
        passes `sync` so lmax / queries-per-shard agree globally.
        make_grad_fn's function consumes this state unchanged inside
        shard_map — per-query lambdas are independent of the blocking,
        so gradients are bit-identical to the serial device path."""
        from jax.sharding import PartitionSpec as P

        from .parallel.mesh import DATA_AXIS

        qb = np.asarray(self.qb, dtype=np.int64)
        qlen = (qb[1:] - qb[:-1]).astype(np.int64)
        nq = len(qb) - 1
        lmax = max(1, int(qlen.max()) if nq else 1)
        bounds = layout.bounds
        nq_cap = max(1, int((bounds[1:] - bounds[:-1]).max()))
        if sync is not None:
            lmax, nq_cap = (int(v) for v in sync([lmax, nq_cap]))
        # same pair-tensor budget as the serial builder: ~16M pair
        # elements per scanned block
        q_block = int(min(max(1, (1 << 24) // (lmax * lmax)),
                          max(nq_cap, 1)))
        nb = max(1, -(-nq_cap // q_block))
        nq_pad = nb * q_block
        S = layout.local_shards
        label = np.asarray(self.metadata.label)

        doc_idx = np.zeros((S, nq_pad, lmax), dtype=np.int32)
        lab = np.full((S, nq_pad, lmax), -1, dtype=np.int32)
        gain = np.zeros((S, nq_pad, lmax), dtype=np.float32)
        wts = np.ones((S, nq_pad, lmax), dtype=np.float32)
        inv = np.zeros((S, nq_pad), dtype=np.float32)
        dead = nq_pad * lmax          # per-shard flat output size
        row_slot = np.full((S, layout.cap), dead, dtype=np.int32)
        ar = np.arange(lmax, dtype=np.int64)
        for s in range(S):
            base = int(qb[bounds[s]])
            for qi, q in enumerate(range(int(bounds[s]),
                                         int(bounds[s + 1]))):
                a, ln = int(qb[q]), int(qlen[q])
                doc_idx[s, qi] = (a - base) + np.minimum(ar,
                                                         max(ln - 1, 0))
                lab[s, qi, :ln] = label[a:a + ln].astype(np.int32)
                gain[s, qi, :ln] = self.label_gain[lab[s, qi, :ln]]
                if self.weights is not None:
                    wts[s, qi, :ln] = self.weights[a:a + ln]
                inv[s, qi] = self.inverse_max_dcgs[q]
                row_slot[s, a - base:a - base + ln] = (
                    qi * lmax + np.arange(ln, dtype=np.int64))

        self._counters = _pair_counters(qlen, S * nq_pad, lmax)
        shp = (S * nb, q_block)
        host = (doc_idx.reshape(shp + (lmax,)),
                lab.reshape(shp + (lmax,)),
                gain.reshape(shp + (lmax,)),
                inv.reshape(shp),
                wts.reshape(shp + (lmax,)),
                row_slot.reshape(-1),
                self.discount.copy())
        specs = (P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                 P(DATA_AXIS, None, None), P(DATA_AXIS, None),
                 P(DATA_AXIS, None, None), P(DATA_AXIS), P())
        return host, specs

    @staticmethod
    def permute_sharded_state_host(host, layout, order_local):
        """Apply a checkpointed ordered-partition row order to the HOST
        sharded state (load_checkpoint restore): re-sorts are shard-
        local, so each shard's doc_idx remaps through the inverse of its
        own block of the order and row_slot rides the permutation —
        exactly make_row_state_fn per shard, done in numpy before the
        device put."""
        di, lab, gain, inv, wts, row_slot, disc = host
        S, cap = layout.local_shards, layout.cap
        nb = di.shape[0] // S
        di = di.copy()
        row_slot = row_slot.reshape(S, cap).copy()
        ordl = np.asarray(order_local).reshape(S, cap)
        for s in range(S):
            rel = ordl[s] - s * cap
            inv_rel = np.argsort(rel).astype(np.int32)
            di[s * nb:(s + 1) * nb] = inv_rel[di[s * nb:(s + 1) * nb]]
            row_slot[s] = row_slot[s][rel]
        return (di, lab, gain, inv, wts, row_slot.reshape(-1), disc)

    @contract.traced_pure
    def make_grad_fn(self):
        sigmoid = float(self.sigmoid)

        def grad_fn(score, state):
            doc_idx, lab, gain, inv, wts, row_slot, disc_table = state
            score = score.astype(jnp.float32)
            n_disc = disc_table.shape[0]

            def block(_, xs):
                di, lb, gn, iv, wb = xs
                valid = lb >= 0
                with jax.named_scope(spans.RANK_GATHER):
                    s = score[di]                       # [QB, L]
                with jax.named_scope(spans.RANK_SORT):
                    s_sort = jnp.where(valid, s, -jnp.inf)
                    # stable descending sort: first-by-score, ties by
                    # index (reference uses non-stable std::sort —
                    # PARITY.md)
                    order = jnp.argsort(-s_sort, axis=-1)
                    rank_of = jnp.argsort(order, axis=-1)
                    dsc = disc_table[jnp.minimum(rank_of, n_disc - 1)]
                    dsc = jnp.where(valid, dsc, 0.0)
                with jax.named_scope(spans.RANK_PAIRS):
                    best = jnp.max(s_sort, axis=-1)
                    worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=-1)
                    norm = (best != worst)[:, None, None]
                    ds = s[:, :, None] - s[:, None, :]      # [QB, L, L]
                    vp = ((lb[:, :, None] > lb[:, None, :])
                          & valid[:, :, None] & valid[:, None, :])
                    delta = ((gn[:, :, None] - gn[:, None, :])
                             * jnp.abs(dsc[:, :, None] - dsc[:, None, :])
                             * iv[:, None, None])
                    delta = jnp.where(
                        norm, delta / (jnp.float32(0.01) + jnp.abs(ds)), delta)
                    # direct sigmoid: the reference's 1M-entry lookup table
                    # (rank_objective.hpp:175-189) is a CPU-era optimization;
                    # a random gather of [QB, L, L] indices serializes on TPU
                    # while the VPU computes exp at full rate.  Values differ
                    # from the table path only by its quantization (~2.5e-5).
                    p_lam = (jnp.float32(2.0)
                             / (jnp.float32(1.0)
                                + jnp.exp(jnp.float32(2.0 * sigmoid) * ds)))
                    p_hess = p_lam * (jnp.float32(2.0) - p_lam)
                    p_lam = jnp.where(vp, p_lam * -delta, 0.0)
                    p_hess = jnp.where(vp, p_hess * jnp.float32(2.0) * delta,
                                       0.0)
                    lam_doc = p_lam.sum(axis=2) - p_lam.sum(axis=1)
                    hess_doc = p_hess.sum(axis=2) + p_hess.sum(axis=1)
                    lam_doc = jnp.where(valid, lam_doc * wb, 0.0)
                    hess_doc = jnp.where(valid, hess_doc * wb, 0.0)
                return None, (lam_doc, hess_doc)

            _, (lam_b, hes_b) = jax.lax.scan(
                block, None, (doc_idx, lab, gain, inv, wts))
            # per-doc outputs land in [nb*QB*L]; every real row owns one
            # slot, so ONE gather (no scatter) maps them back to [n_pad].
            # Padded rows carry the DEAD slot (pad_to) and read the
            # appended zero cell — no positional live-row assumption, so
            # the mapping survives ordered-partition row permutations.
            with jax.named_scope(spans.RANK_GATHER):
                zero = jnp.zeros((1,), dtype=jnp.float32)
                lam_flat = jnp.concatenate([lam_b.reshape(-1), zero])
                hes_flat = jnp.concatenate([hes_b.reshape(-1), zero])
                return lam_flat[row_slot], hes_flat[row_slot]

        return grad_fn

    def _sigmoid_lut(self, s: np.ndarray) -> np.ndarray:
        idx = ((s - self.min_in) * self.idx_factor).astype(np.int64)
        idx = np.clip(idx, 0, _SIGMOID_BINS - 1)
        out = self.sigmoid_table[idx]
        out = np.where(s <= self.min_in, self.sigmoid_table[0], out)
        out = np.where(s >= self.max_in, self.sigmoid_table[-1], out)
        return out

    def get_gradients(self, score):
        if self.impl == "device":
            return self._dev_fn(jnp.asarray(score), self._dev_state)
        score_np = np.asarray(score, dtype=np.float32)
        # Reference-order native path: bit-parity with the golden models
        # needs libstdc++ std::sort tie permutations and sequential fp32
        # pair accumulation (rank_objective.hpp:76-164) — see native/.
        res = native.lambdarank_grads(
            score_np[:self.num_data], self.metadata.label, self.qb,
            self.inverse_max_dcgs, self.label_gain, self.discount,
            self.sigmoid_table, self.min_in, self.max_in, self.idx_factor,
            self.weights, self.n_pad)
        if res is not None:
            return jnp.asarray(res[0]), jnp.asarray(res[1])
        # padded rows (beyond the last query boundary) stay zero
        lambdas = np.zeros(self.n_pad, dtype=np.float32)
        hessians = np.zeros(self.n_pad, dtype=np.float32)
        label = self.metadata.label
        for q in range(len(self.qb) - 1):
            a, b = int(self.qb[q]), int(self.qb[q + 1])
            self._one_query(score_np[a:b], label[a:b],
                            self.inverse_max_dcgs[q],
                            lambdas[a:b], hessians[a:b])
        if self.weights is not None:
            lambdas[:self.num_data] *= self.weights
            hessians[:self.num_data] *= self.weights
        return jnp.asarray(lambdas), jnp.asarray(hessians)

    def _one_query(self, score, label, inv_max_dcg, lambdas, hessians):
        """Vectorized pairwise lambdas for one query
        (reference rank_objective.hpp:76-164)."""
        cnt = len(score)
        if cnt == 0 or inv_max_dcg <= 0:
            return
        order = np.argsort(-score, kind="stable")
        rank_of = np.empty(cnt, dtype=np.int64)
        rank_of[order] = np.arange(cnt)
        best = score[order[0]]
        worst_idx = cnt - 1
        if worst_idx > 0 and score[order[worst_idx]] == K_MIN_SCORE:
            worst_idx -= 1
        worst = score[order[worst_idx]]

        lab_i = label.astype(np.int64)
        gain = self.label_gain[lab_i].astype(np.float32)     # [L]
        disc = self.discount[rank_of].astype(np.float32)     # [L]

        # pair (h, l): labels[h] > labels[l]
        hi = lab_i[:, None] > lab_i[None, :]
        valid = hi & (score[None, :] != K_MIN_SCORE) \
                   & (score[:, None] != K_MIN_SCORE)
        if not valid.any():
            return
        ds = (score[:, None] - score[None, :]).astype(np.float32)
        dcg_gap = gain[:, None] - gain[None, :]
        paired_disc = np.abs(disc[:, None] - disc[None, :])
        delta = (dcg_gap * paired_disc * np.float32(inv_max_dcg))
        if best != worst:
            delta = delta / (np.float32(0.01) + np.abs(ds))
        p_lambda = self._sigmoid_lut(ds)
        p_hess = p_lambda * (np.float32(2.0) - p_lambda)
        p_lambda = p_lambda * -delta
        p_hess = p_hess * np.float32(2.0) * delta
        p_lambda = np.where(valid, p_lambda, 0.0).astype(np.float32)
        p_hess = np.where(valid, p_hess, 0.0).astype(np.float32)
        lambdas += p_lambda.sum(axis=1) - p_lambda.sum(axis=0)
        hessians += p_hess.sum(axis=1) + p_hess.sum(axis=0)


def _pair_counters(qlen: np.ndarray, padded_queries: int, lmax: int) -> dict:
    """What the device path's pair pass costs a tree: the [L, L] cells it
    evaluates over the padded query blocks (all shards) against the cells
    the queries hold, sum L_q^2."""
    return {"pairs_padded": int(padded_queries) * lmax * lmax,
            "pairs_real": int((qlen.astype(np.int64) ** 2).sum()),
            "queries": int(len(qlen)), "lmax": int(lmax)}


def default_label_gain():
    # 2^i - 1 (reference src/io/config.cpp:221-227)
    return [0.0] + [float((1 << i) - 1) for i in range(1, 31)]


def check_rank_label(label: np.ndarray, num_gains: int) -> None:
    """Labels must index label_gain (reference dcg_calculator.cpp:65's
    Log::Fatal, checked up front here because the native kernels index
    label_cnt/label_gain without bounds checks)."""
    lab = np.asarray(label)
    if len(lab) and (lab.min() < 0 or lab.max() >= num_gains):
        log.fatal("Ranking label out of range of label_gain: %g"
                  % (lab.min() if lab.min() < 0 else lab.max()))


def max_dcg_at_k(k: int, label: np.ndarray, label_gain: np.ndarray,
                 discount: np.ndarray) -> float:
    """DCGCalculator::CalMaxDCGAtK (reference dcg_calculator.cpp:34-57)."""
    lab = np.sort(label.astype(np.int64))[::-1]
    k = min(k, len(lab))
    return float((label_gain[lab[:k]] * discount[:k]).sum())


def create_objective(config: Config) -> Optional[Objective]:
    t = config.objective
    if t == "regression":
        return RegressionL2(config)
    if t == "binary":
        return BinaryLogloss(config)
    if t == "multiclass":
        return MulticlassSoftmax(config)
    if t == "lambdarank":
        return LambdarankNDCG(config)
    if t == "none":
        return None
    log.fatal("Unknown objective type %s" % t)
