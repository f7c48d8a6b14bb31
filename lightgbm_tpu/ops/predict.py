"""Vectorized tree traversal.

Replaces the reference's per-row pointer-chasing (Tree::GetLeaf,
include/LightGBM/tree.h:166-189) with a data-parallel iterate: all rows step
down one level per loop iteration via gathers — the loop is over tree depth,
not over rows, so the work is [N]-wide vector ops that XLA maps onto the VPU.
"""

from __future__ import annotations

import functools

from ..utils.compile_cache import enable_compilation_cache

enable_compilation_cache()   # before any jit traces (was a package-import side effect)

import jax
import jax.numpy as jnp

# host-side exact-compare helpers live in predict_host.py (pure numpy,
# importable from jax-free lanes); re-exported here for the historical
# import site every route uses
from .predict_host import (matmul_host_arrays, order_key,  # noqa: F401
                           rank_encode, split_hi_lo,
                           threshold_rank_tables)


@jax.jit
def predict_leaf_binned(split_feature: jax.Array, threshold_bin: jax.Array,
                        left_child: jax.Array, right_child: jax.Array,
                        bins_t: jax.Array) -> jax.Array:
    """Leaf index per row from binned features.

    Mirrors Tree::GetLeaf over BinIterators (tree.h:166-177): node>=0 walks,
    leaves are encoded ~leaf in the child arrays. Returns [N] i32 leaf ids.
    """
    n = bins_t.shape[1]
    node = jnp.zeros(n, dtype=jnp.int32)
    # a well-formed tree reaches its leaf in < num_nodes steps; the bound
    # makes degenerate inputs (an unsplit stump's all-zero child arrays,
    # e.g. an untouched DART-bank row) terminate at node 0 -> ~0 = -1,
    # which gathers the zero-valued dummy leaf slot instead of spinning
    # the while_loop forever
    max_steps = split_feature.shape[0] + 1

    def cond(carry):
        i, node = carry
        return (i < max_steps) & jnp.any(node >= 0)

    def body(carry):
        i, node = carry
        idx = jnp.maximum(node, 0)
        feat = split_feature[idx]
        thr = threshold_bin[idx]
        val = bins_t[feat, jnp.arange(n)].astype(jnp.int32)
        nxt = jnp.where(val <= thr, left_child[idx], right_child[idx])
        return i + 1, jnp.where(node >= 0, nxt, node)

    _, node = jax.lax.while_loop(cond, body, (jnp.int32(0), node))
    return ~node


def replay_leaf_binned(split_feature: jax.Array, threshold_bin: jax.Array,
                       left_child: jax.Array, num_leaves: jax.Array,
                       bins_t: jax.Array, dtype=jnp.int32) -> jax.Array:
    """predict_leaf_binned's answer for a tree the grow scan made, by
    replaying its splits in the order they were made: step k read ONE bin
    row and sent the rows of the leaf it split that lie over the threshold
    to the new leaf k + 1 (ops/grow.py `step`; node k's left child keeps the
    split leaf's id, so that leaf is where going left from node k ends).
    The same compares as the scan's own partition, so the ids are its ids.

    For rows in BULK: a pass streams one bin row and the ids (5 bytes a
    row read, 4 written) where the descent gathers a byte per row and
    LEVEL at 25 ns an index (PERF.md section 6, PR 33: 13.6M out-of-bag rows
    took 2.50 s a tree by descent, 0.049 s by replay).  Returns [N] leaf
    ids of `dtype` (a byte a row where the leaves fit in one)."""
    nodes = split_feature.shape[0]

    def go_left(_, at):
        return jnp.where(at >= 0, left_child[jnp.maximum(at, 0)], at)
    source = ~jax.lax.fori_loop(0, nodes, go_left,
                                jnp.arange(nodes, dtype=jnp.int32))

    def split(k, leaf):
        row = jax.lax.dynamic_index_in_dim(bins_t, split_feature[k], 0,
                                           keepdims=False)
        go_right = ((k < num_leaves - 1) & (leaf == source[k])
                    & (row.astype(jnp.int32) > threshold_bin[k]))
        return jnp.where(go_right, (k + 1).astype(dtype), leaf)
    return jax.lax.fori_loop(0, nodes - 1, split,
                             jnp.zeros(bins_t.shape[1], dtype=dtype))


# The body itself, for a caller that is traced twice under two scopes (DART's
# drop and normalise): a jitted function's trace is kept, and with it the
# scope names of whoever traced it first.
replay_leaf_binned_inline = replay_leaf_binned
replay_leaf_binned = jax.jit(replay_leaf_binned)


@jax.jit
def predict_leaf_raw(split_feature_real: jax.Array, threshold: jax.Array,
                     left_child: jax.Array, right_child: jax.Array,
                     x: jax.Array) -> jax.Array:
    """Leaf index per row from raw feature values (Tree::GetLeaf, tree.h:179-189).

    x: [N, F_total] float; split rule `value <= threshold` goes left.
    """
    n = x.shape[0]
    node = jnp.zeros(n, dtype=jnp.int32)

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        idx = jnp.maximum(node, 0)
        feat = split_feature_real[idx]
        thr = threshold[idx]
        val = x[jnp.arange(n), feat]
        nxt = jnp.where(val <= thr, left_child[idx], right_child[idx])
        return jnp.where(node >= 0, nxt, node)

    node = jax.lax.while_loop(cond, body, node)
    return ~node


def _leaf_hi_lo_inner(split_feature_real, thr_hi, thr_lo, left_child,
                      right_child, x_hi, x_lo):
    """One tree's descent for all rows: value <= threshold via exact
    lexicographic uint32-pair compare of split_hi_lo keys."""
    n = x_hi.shape[0]
    rows = jnp.arange(n)
    node = jnp.zeros(n, dtype=jnp.int32)

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        idx = jnp.maximum(node, 0)
        feat = split_feature_real[idx]
        vh = x_hi[rows, feat]
        vl = x_lo[rows, feat]
        th = thr_hi[idx]
        tl = thr_lo[idx]
        left = (vh < th) | ((vh == th) & (vl <= tl))
        nxt = jnp.where(left, left_child[idx], right_child[idx])
        return jnp.where(node >= 0, nxt, node)

    return ~jax.lax.while_loop(cond, body, node)


@functools.partial(jax.jit, static_argnames=("tree_block",))
def predict_leaf_matmul(sel: jax.Array, thr_code: jax.Array,
                        path_pos: jax.Array, path_neg: jax.Array,
                        leaf_depth: jax.Array, x_code: jax.Array,
                        *, tree_block: int) -> jax.Array:
    """Gather-free whole-model leaf indices — the TPU-native predictor.

    Pointer-chasing descents (tree.h:179-189) need one random gather per
    level per tree, which serializes on TPU.  Instead the traversal is
    re-expressed as matmuls + an argmax:

      1. node comparisons: the host rank-encodes each value against its
         feature's model-threshold table (rank_encode — exact f64
         order), a one-hot selection matmul routes the codes to nodes,
         and `code <= node_rank` reproduces `value <= threshold`:
         cmp [C, T*M].
      2. leaf resolution: a leaf is reached iff every node on its path
         branched toward it.  With path matrices P± [T, M, L] (+1 node
         sends the leaf left, -1 right), score = cmp @ P+ + (1-cmp) @ P-
         counts satisfied path conditions; score - depth is 0 exactly
         for the reached leaf and <= -1 otherwise, so an argmax over L
         recovers the leaf with no data-dependent memory access.

    Trees process in blocks of `tree_block` via lax.scan to bound the
    [C, tb*M] temporaries.  sel [Ftot, T*M] f32; thr_code [T*M] f32;
    path_pos/neg [T, M, L]; leaf_depth [T, L] (+inf padding slots);
    x_code [C, Ftot] uint16.  Returns [C, T] i32.
    """
    c, ftot = x_code.shape
    t_total = path_pos.shape[0]
    m = path_pos.shape[1]
    nb = t_total // tree_block

    sel_b = sel.reshape(ftot, nb, tree_block * m).transpose(1, 0, 2)
    thr_b = thr_code.reshape(nb, tree_block * m)
    pos_b = path_pos.reshape(nb, tree_block, m, -1)
    neg_b = path_neg.reshape(nb, tree_block, m, -1)
    dep_b = leaf_depth.reshape(nb, tree_block, 1, -1)
    xf = x_code.astype(jnp.float32)              # [C, Ftot], ints < 2^16

    def block(_, args):
        s, th, pp, pn, dp = args
        # HIGHEST precision: codes are integers up to 65535 and the
        # TPU's default bf16 matmul (8 mantissa bits) would corrupt
        # them; the 3-pass f32 mode is exact for one-hot selections
        xsel = jnp.einsum("cf,fm->cm", xf, s,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        cmp = (xsel <= th[None]).astype(jnp.float32)         # [C, tb*m]
        cmp = cmp.reshape(c, tree_block, m).transpose(1, 0, 2)
        score = (jnp.einsum("tcm,tml->tcl", cmp, pp,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("tcm,tml->tcl", 1.0 - cmp, pn,
                              preferred_element_type=jnp.float32))
        leaf = jnp.argmax(score - dp, axis=-1)               # [tb, C]
        # uint8 when it fits: the [C, T] result is the bulk of the
        # device->host traffic and leaves index at most
        # max_leaves <= 256 slots
        out_dt = jnp.uint8 if path_pos.shape[2] <= 256 else jnp.int32
        return None, leaf.astype(out_dt)

    _, leaves = jax.lax.scan(block, None, (sel_b, thr_b, pos_b, neg_b,
                                           dep_b))
    return leaves.reshape(t_total, c).T


@functools.partial(jax.jit, static_argnames=("num_class",))
def accumulate_scores(leaves: jax.Array, leaf_values: jax.Array,
                      *, num_class: int) -> jax.Array:
    """On-device f64 score accumulation in boosting order.

    EXACTLY the host loop of GBDT.predict_raw (`out[i % k] +=
    leaf_values[i, leaves[:, i]]` for i ascending — the reference
    predictor's += tree->Predict, predictor.hpp:35-70): a lax.scan over
    trees performs the same sequence of f64 additions per row, so the
    result is bit-identical to the host path while the device->host
    transfer shrinks from [C, T] leaf indices to [K, C] doubles.
    Requires x64 (the CLI predict path enables it on accelerators).

    leaves [C, T] int; leaf_values [T, L] f64.  Returns [K, C] f64.
    """
    c = leaves.shape[0]
    t = leaf_values.shape[0]
    # graftlint: disable=GL003 -- f64 IS the contract here: this kernel
    # replicates the host's double score accumulation bit-for-bit and
    # only runs when the CLI predict path enabled x64 (cli.init_predict)
    out = jnp.zeros((num_class, c), dtype=jnp.float64)

    def step(s, inp):
        i, lv_t, leaf_t = inp
        return s.at[i % num_class].add(lv_t[leaf_t]), None

    out, _ = jax.lax.scan(
        step, out,
        (jnp.arange(t, dtype=jnp.int32), leaf_values,
         leaves.T.astype(jnp.int32)))
    return out


@jax.jit
def predict_leaf_stacked(split_feature_real: jax.Array, thr_hi: jax.Array,
                         thr_lo: jax.Array, left_child: jax.Array,
                         right_child: jax.Array, x_hi: jax.Array,
                         x_lo: jax.Array) -> jax.Array:
    """Whole-model leaf indices on device.

    The reference predicts row-by-row, tree-by-tree on the host
    (predictor.hpp:35-70 over Tree::GetLeaf, tree.h:179-189); here every
    tree's node arrays are stacked into [T, M] tensors and a lax.scan
    walks the model while all rows descend each tree data-parallel on
    the VPU.  Only the traversal runs on device — score accumulation
    happens on the host in f64 from the returned indices (gbdt.py
    predict_raw), keeping output formatting byte-identical to the
    reference under any backend/x64 configuration.

    split_feature_real/thr_hi/thr_lo/left_child/right_child: [T, M]
    padded node arrays (a 1-leaf stump encodes left_child[0] == ~0 so
    every row lands in leaf 0); x_hi/x_lo: [C, F_total] f32 pair.
    Returns [C, T] i32 leaf indices.
    """

    def per_tree(_, t):
        sf_t, th_t, tl_t, lc_t, rc_t = t
        return None, _leaf_hi_lo_inner(sf_t, th_t, tl_t, lc_t, rc_t,
                                       x_hi, x_lo)

    _, leaves = jax.lax.scan(
        per_tree, None,
        (split_feature_real, thr_hi, thr_lo, left_child, right_child))
    return leaves.T
