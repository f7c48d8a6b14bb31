"""Vectorized best-split search.

Replaces FeatureHistogram::FindBestThreshold's right-to-left scalar scan
(reference src/treelearner/feature_histogram.hpp:112-170) with suffix sums +
masked argmax over all (feature, threshold) pairs at once — one fused XLA
computation instead of an OpenMP loop over features.

Exact semantic parity notes (all verified against the reference source):
  - right-side hessian starts at kEpsilon = 1e-15 (hpp:119)
  - thresholds scanned are t in [1, B); stored threshold is t-1; split rule
    is `bin <= threshold` goes left (hpp:125,152)
  - the `break` conditions on left stats are monotone in t, so they are
    equivalent to masks
  - gains >= gain_shift + min_gain_to_split are eligible (hpp:143, `<` skips)
  - within a feature, ties keep the LARGER threshold (descending scan with
    strict `>` replacement, hpp:148)
  - across features, ties keep the SMALLER feature index
    (SplitInfo::MaxReducer, src/treelearner/split_info.hpp:98-103)
  - L1/L2 regularized gain and leaf output (hpp:224-245)
"""

from __future__ import annotations

from typing import NamedTuple

from ..utils.compile_cache import enable_compilation_cache

enable_compilation_cache()   # before any jit traces (was a package-import side effect)

import jax
import jax.numpy as jnp

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf


class SplitParams(NamedTuple):
    """Static split hyper-parameters (baked into the jit)."""
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    lambda_l1: float
    lambda_l2: float
    min_gain_to_split: float


class BestSplit(NamedTuple):
    """Per-leaf best split candidate — SplitInfo as a struct of scalars
    (reference src/treelearner/split_info.hpp:14-54)."""
    gain: jax.Array          # f, kMinScore when invalid
    feature: jax.Array       # i32 inner feature index
    threshold: jax.Array     # i32 bin threshold (left: bin <= threshold)
    left_count: jax.Array    # i32
    right_count: jax.Array   # i32
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    left_output: jax.Array
    right_output: jax.Array


def leaf_split_gain(sum_g, sum_h, l1: float, l2: float):
    """GetLeafSplitGain (reference feature_histogram.hpp:224-231)."""
    abs_g = jnp.abs(sum_g)
    reg = jnp.maximum(abs_g - l1, 0.0)
    return jnp.where(abs_g > l1, reg * reg / (sum_h + l2), 0.0)


def leaf_output(sum_g, sum_h, l1: float, l2: float):
    """CalculateSplittedLeafOutput (reference feature_histogram.hpp:239-245)."""
    abs_g = jnp.abs(sum_g)
    val = -jnp.sign(sum_g) * (abs_g - l1) / (sum_h + l2)
    return jnp.where(abs_g > l1, val, 0.0)


def _split_scan(hist: jax.Array, leaf_count, sum_g, sum_h,
                feature_mask: jax.Array, params: SplitParams):
    """The suffix-sum threshold scan shared by the serial argmax and the
    voting learner's per-feature vote.  Returns per-(feature, bin) arrays:
    (masked_gains, left_g, left_h, left_cnt, right_g, right_h, right_cnt,
    gain_shift)."""
    l1, l2 = params.lambda_l1, params.lambda_l2
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    c = hist[:, :, 2]

    # suffix sums over bins: right side of a split at t covers bins >= t
    right_g = jnp.cumsum(g[:, ::-1], axis=1)[:, ::-1]
    right_h = jnp.cumsum(h[:, ::-1], axis=1)[:, ::-1] + K_EPSILON
    right_c = jnp.cumsum(c[:, ::-1], axis=1)[:, ::-1]
    right_cnt = jnp.round(right_c).astype(jnp.int32)

    left_g = sum_g - right_g
    left_h = sum_h - right_h
    left_cnt = leaf_count - right_cnt

    gain_shift = leaf_split_gain(sum_g, sum_h, l1, l2)
    min_gain_shift = gain_shift + params.min_gain_to_split

    gains = (leaf_split_gain(left_g, left_h, l1, l2)
             + leaf_split_gain(right_g, right_h, l1, l2))

    valid = ((right_cnt >= params.min_data_in_leaf)
             & (left_cnt >= params.min_data_in_leaf)
             & (right_h >= params.min_sum_hessian_in_leaf)
             & (left_h >= params.min_sum_hessian_in_leaf)
             & (gains >= min_gain_shift))
    # t = 0 is not a split (everything right); mask bin 0
    valid = valid.at[:, 0].set(False)
    valid = valid & feature_mask[:, None]

    masked_gains = jnp.where(valid, gains, K_MIN_SCORE)
    return (masked_gains, left_g, left_h, left_cnt, right_g, right_h,
            right_cnt, gain_shift)


def _per_feature_argmax(masked_gains: jax.Array):
    """Per-feature best threshold with the larger-t tie-break: argmax over
    REVERSED bins (descending scan with strict `>` replacement keeps the
    larger threshold, reference feature_histogram.hpp:148).
    -> (best_gain [F], best_t [F])."""
    b = masked_gains.shape[1]
    rev = masked_gains[:, ::-1]
    best_rev_idx = jnp.argmax(rev, axis=1)
    best_t = b - 1 - best_rev_idx
    best_gain_f = jnp.take_along_axis(masked_gains, best_t[:, None],
                                      axis=1)[:, 0]
    return best_gain_f, best_t


def per_feature_best(hist: jax.Array, leaf_count, sum_g, sum_h,
                     feature_mask: jax.Array, params: SplitParams):
    """(best_gain [F], best_threshold_bin t [F]) per feature — the local
    scoring pass of the voting learner (PV-Tree's local voting step)."""
    masked_gains = _split_scan(hist, leaf_count, sum_g, sum_h,
                               feature_mask, params)[0]
    return _per_feature_argmax(masked_gains)


def find_best_split(hist: jax.Array, leaf_count: jax.Array,
                    sum_g: jax.Array, sum_h: jax.Array,
                    feature_mask: jax.Array, params: SplitParams) -> BestSplit:
    """Best split over one leaf's histograms.

    hist:         [F, B, 3] (grad, hess, count) per (feature, bin)
    leaf_count:   scalar i32 — rows in this leaf (bagged, or global when
                  data-parallel, matching data_parallel_tree_learner.cpp:155-186)
    sum_g/sum_h:  scalar leaf totals
    feature_mask: [F] bool — feature_fraction sample for this tree
    """
    dt = hist.dtype
    l1, l2 = params.lambda_l1, params.lambda_l2

    (masked_gains, left_g, left_h, left_cnt, right_g, right_h, right_cnt,
     gain_shift) = _split_scan(hist, leaf_count, sum_g, sum_h,
                               feature_mask, params)

    best_gain_f, best_t = _per_feature_argmax(masked_gains)

    # across features: first max = smaller feature index
    best_f = jnp.argmax(best_gain_f).astype(jnp.int32)
    t = best_t[best_f].astype(jnp.int32)
    gain = best_gain_f[best_f]

    bl_g = left_g[best_f, t]
    bl_h = left_h[best_f, t]
    br_g = right_g[best_f, t]
    br_h = right_h[best_f, t]
    bl_c = left_cnt[best_f, t]
    br_c = right_cnt[best_f, t]

    # reference reports sums re-derived from parent totals (hpp:164-168):
    # right = parent - left, with left kept from the scan. Our left/right are
    # both scan-derived; recompute right from totals for bit-parity.
    br_g = sum_g - bl_g
    br_h = sum_h - bl_h

    return BestSplit(
        gain=gain - gain_shift,
        feature=best_f,
        threshold=t - 1,
        left_count=bl_c,
        right_count=br_c,
        left_sum_g=bl_g.astype(dt),
        left_sum_h=bl_h.astype(dt),
        right_sum_g=br_g.astype(dt),
        right_sum_h=br_h.astype(dt),
        left_output=leaf_output(bl_g, bl_h, l1, l2).astype(dt),
        right_output=leaf_output(br_g, br_h, l1, l2).astype(dt),
    )
