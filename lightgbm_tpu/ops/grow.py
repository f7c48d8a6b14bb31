"""Leaf-wise tree growth as one jitted fixed-trip `lax.scan`.

TPU-native redesign of SerialTreeLearner::Train
(reference src/treelearner/serial_tree_learner.cpp:100-134):

  - The reference's DataPartition (grouped row-index arrays, re-shuffled at
    every split) becomes a flat per-row `leaf_id [N] int32`, updated with one
    vectorized compare per split — no data movement, shard-local under pjit.
    In the block-list mode (`ranged`) the compare is a kernel over the row
    blocks the split leaf occupies, which are kept as state.
  - Per-leaf histogram cache (HistogramPool) becomes a dense
    `hist [L, F, B, 3]` tensor; the parent-minus-smaller-child subtraction
    trick (FeatureHistogram::Subtract, feature_histogram.hpp:97-106) is a
    tensor subtract, halving histogram work exactly as in the reference.
  - The whole `num_leaves - 1` split loop runs on-device inside one
    compiled fixed-trip scan; host sees a single call per tree.

Out-of-bag rows keep following splits via leaf_id (they are masked out of
histograms by bag_mask); this makes the final score update a single
`leaf_value[leaf_id]` gather for ALL rows, which is exactly equivalent to
the reference's two-path update (partition fast path + OOB traversal,
src/boosting/gbdt.cpp:162-167, score_updater.hpp:44-68).

For data-parallel training, `psum_axis` names a mesh axis: local histograms
and root sums are all-reduced over it (the moral equivalent of the
reference's ReduceScatter of histogram buffers,
src/treelearner/data_parallel_tree_learner.cpp:124-154), after which every
shard computes the identical split — the same invariant the reference
relies on (global counts, data_parallel_tree_learner.cpp:226-232).

The phases carry `jax.named_scope`s from utils/spans.py (root sweep,
block list, sweep, pool, exchange, gain scan, partition, tree update):
HLO metadata, so a profiler trace can be split by phase
(benchmark/phase_table.py) at no cost to the compiled step.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..analysis.contracts import contract
from ..utils import spans
from .histogram import leaf_histogram, make_gvals
from .predict import replay_leaf_binned
from .split import (BestSplit, SplitParams, find_best_split, K_MIN_SCORE,
                    per_feature_best)


class TreeArrays(NamedTuple):
    """Array-based binary tree, mirroring reference include/LightGBM/tree.h:125-152.
    Leaves encoded as ~leaf_idx in child pointers.  Each array carries one
    trailing DUMMY slot (node index L-1 / leaf index L) that inactive scan
    steps write into — real entries are nodes [0, L-2] and leaves [0, L-1];
    the dummy is unreachable from traversal and trimmed on host export."""
    split_feature: jax.Array    # [L] i32 inner (used-feature) index
    threshold_bin: jax.Array    # [L] i32
    split_gain: jax.Array       # [L] f
    left_child: jax.Array       # [L] i32
    right_child: jax.Array      # [L] i32
    leaf_parent: jax.Array      # [L+1] i32
    leaf_value: jax.Array       # [L+1] f
    internal_value: jax.Array   # [L] f
    leaf_depth: jax.Array       # [L+1] i32
    leaf_count: jax.Array       # [L+1] i32
    num_leaves: jax.Array       # scalar i32
    # what the tree's block-list sweeps cost, root included and summed
    # over shards (0 in the other sweep modes): occupied row blocks, and
    # the row steps the kernels' grids ran; the row blocks its split
    # leaves occupied, which the partition passes visited; and the in-bag
    # rows of the leaves the sweeps targeted, which would fill
    # rows_swept / PALLAS_ROW_BLOCK blocks
    blocks_swept: jax.Array     # scalar i32
    grid_rows: jax.Array        # scalar i32
    partition_blocks: jax.Array  # scalar i32
    rows_swept: jax.Array       # scalar i32


class GrowState(NamedTuple):
    tree: TreeArrays
    leaf_id: jax.Array          # [N] i32; in the block-list mode under
    #                             the row's bag bit (hist_pallas.OOB_BIT)
    occ: jax.Array              # [L+1, nblocks] bool: the row blocks that
    #                             hold a row of each leaf, and
    occ_bag: jax.Array          # the same of in-bag rows (what a sweep
    #                             lists); block-list mode, else empty
    hist: jax.Array             # [K+1, F, B, 3] (last = dummy slot);
    #                             K = max_leaves (dense) or hist_slots (pool)
    leaf_sum_g: jax.Array       # [L+1] (last = dummy slot)
    leaf_sum_h: jax.Array       # [L+1]
    best_f: jax.Array           # [L+1, 8] float best-split fields
    best_i: jax.Array           # [L+1, 4] i32 best-split fields
    swept: jax.Array            # [4] i32 so far: (occupied blocks, grid
    #                             rows) of the sweeps, blocks partitioned,
    #                             rows the sweeps targeted
    # histogram-pool bookkeeping (HistogramPool, reference
    # feature_histogram.hpp:275-398, re-designed as on-device LRU): only
    # carried when hist_slots bounds the pool; zero-size arrays otherwise
    leaf_slot: jax.Array        # [L+1] i32 slot of leaf's hist, -1 evicted
    slot_leaf: jax.Array        # [K+1] i32 leaf occupying slot, -1 free
    slot_used: jax.Array        # [K+1] i32 last-used scan step (LRU key)


# column layout of the packed per-leaf best-split state.  Packing the
# 11 BestSplit fields into two stacked arrays turns the per-split
# bookkeeping (2 leaves updated, 1 read) into 6 row-sized ops instead of
# ~33 scalar gathers/updates — every extra op in the sequential split
# chain costs launch latency.
BF_GAIN, BF_LG, BF_LH, BF_RG, BF_RH, BF_LOUT, BF_ROUT = range(7)
BI_FEAT, BI_THR, BI_LCNT, BI_RCNT = range(4)


def _pack_best(s: BestSplit, dtype):
    bf = jnp.stack([s.gain.astype(dtype), s.left_sum_g.astype(dtype),
                    s.left_sum_h.astype(dtype), s.right_sum_g.astype(dtype),
                    s.right_sum_h.astype(dtype), s.left_output.astype(dtype),
                    s.right_output.astype(dtype),
                    jnp.zeros((), dtype)])
    bi = jnp.stack([s.feature, s.threshold, s.left_count, s.right_count])
    return bf, bi


def _empty_tree(max_leaves: int, dtype) -> TreeArrays:
    L = max_leaves
    z_i = functools.partial(jnp.zeros, dtype=jnp.int32)
    z_f = functools.partial(jnp.zeros, dtype=dtype)
    return TreeArrays(
        split_feature=z_i(L), threshold_bin=z_i(L), split_gain=z_f(L),
        left_child=z_i(L), right_child=z_i(L),
        leaf_parent=jnp.full(L + 1, -1, dtype=jnp.int32),
        leaf_value=z_f(L + 1), internal_value=z_f(L),
        leaf_depth=jnp.ones(L + 1, dtype=jnp.int32),
        leaf_count=z_i(L + 1),
        num_leaves=jnp.int32(1),
        blocks_swept=jnp.int32(0), grid_rows=jnp.int32(0),
        partition_blocks=jnp.int32(0), rows_swept=jnp.int32(0),
    )


def _empty_best_packed(max_leaves: int, dtype):
    bf = jnp.zeros((max_leaves + 1, 8), dtype=dtype)
    bf = bf.at[:, BF_GAIN].set(K_MIN_SCORE)
    bi = jnp.zeros((max_leaves + 1, 4), dtype=jnp.int32)
    return bf, bi


def _reduce_best_over_features(s: BestSplit, f_offset, feature_axis: str
                               ) -> BestSplit:
    """Combine per-shard best splits into the global best, replicated.

    The TPU equivalent of FeatureParallelTreeLearner's
    Allreduce(SplitInfo::MaxReducer) (reference
    src/treelearner/feature_parallel_tree_learner.cpp:45-78 and
    split_info.hpp:56-104): max gain, ties broken by the SMALLER global
    feature index, so every shard picks the identical winner.
    """
    glob = s._replace(feature=s.feature + f_offset)
    with jax.named_scope(spans.HIST_EXCHANGE):
        gathered = jax.tree_util.tree_map(
            lambda a: jax.lax.all_gather(a, feature_axis), glob)
    mx = jnp.max(gathered.gain)
    eligible = gathered.gain == mx
    win = jnp.argmin(jnp.where(eligible, gathered.feature,
                               jnp.iinfo(jnp.int32).max))
    return jax.tree_util.tree_map(lambda a: a[win], gathered)


@contract.traced_pure
@contract.parity_oracle("the growth kernel under full-length masked "
                        "bagging: bag_rows<=0 falls through here — the "
                        "bit-parity oracle bag compaction is tested "
                        "against (PARITY.md §2.3)")
@functools.partial(
    jax.jit,
    static_argnames=("max_leaves", "max_bin", "params", "max_depth",
                     "row_chunk", "psum_axis", "feature_axis",
                     "voting_top_k", "hist_impl", "hist_agg", "num_shards",
                     "hist_slots", "ranged"))
def grow_tree(bins_t: jax.Array, grad: jax.Array, hess: jax.Array,
              bag_mask: jax.Array, feature_mask: jax.Array, *,
              max_leaves: int, max_bin: int, params: SplitParams,
              max_depth: int = -1, row_chunk: int = 0,
              psum_axis: Optional[str] = None,
              feature_axis: Optional[str] = None,
              voting_top_k: int = 0, hist_impl: str = "xla",
              hist_agg: str = "psum", num_shards: int = 0,
              hist_slots: int = 0, ranged: bool = False):
    """Grow one leaf-wise tree. Returns (TreeArrays, leaf_id [N] i32).

    bins_t [F, N] uint8; grad/hess [N]; bag_mask [N] bool;
    feature_mask [F] bool. All per-split control flow is on-device.
    hist_impl: "xla" (portable one-hot matmul) or "pallas" (TPU radix
    kernel, f32, max_bin<=256, N % 8192 == 0).
    ranged (pallas): per split, sweep only the row blocks that hold the
    target leaf's rows (leaf_histogram_blocklist) instead of every block
    (leaf_histogram_masked), and partition only those of the split leaf
    (leaf_partition_blocklist) instead of comparing every row;
    bit-identical for the same row order.
    psum_axis: mesh axis sharding rows (tree_learner=data).
    hist_slots (>0): bound histogram HBM to hist_slots live [F, B, 3]
    leaf histograms — the reference HistogramPool's role
    (feature_histogram.hpp:275-398) without its host LRU machinery: an
    on-device slot pool inside the scan, least-recently-used eviction,
    and a full recompute of the parent histogram when it was evicted
    (the reference recomputes evicted leaves the same way).  0 keeps the
    dense [max_leaves+1, F, B, 3] tensor (every leaf cached; exactly the
    subtraction-trick arithmetic of the reference's unbounded default,
    histogram_pool_size=-1).
    hist_agg (with psum_axis): "psum" all-reduces the full histogram
    tensor; "scatter" is the owner-computes protocol of the reference
    (ReduceScatter + per-owner FindBestThreshold,
    data_parallel_tree_learner.cpp:124-187): `psum_scatter` gives each
    shard the GLOBAL histograms of F/num_shards features, each shard
    scans only those, and an all-gather of the per-shard best
    candidates + argmax replaces Allreduce(SplitInfo::MaxReducer) —
    halving per-split ICI traffic vs "psum".  Needs static num_shards.
    feature_axis: mesh axis sharding features (tree_learner=feature) —
    bins_t/feature_mask hold this shard's features; rows are replicated;
    tree arrays come out replicated with GLOBAL feature indices.
    voting_top_k (>0, with psum_axis): tree_learner=voting — PV-Tree
    two-round voting (absent from the reference snapshot, SURVEY.md §2.9;
    design per the LightGBM paper): histograms stay shard-local, each
    shard votes its top-k features by local gain, and only the 2k
    vote-winning features' histograms are all-reduced, cutting per-split
    traffic from O(F*B) to O(2k*B).
    """
    f, n = bins_t.shape
    dtype = grad.dtype
    voting = voting_top_k > 0 and psum_axis is not None
    scatter = (hist_agg == "scatter" and psum_axis is not None
               and not voting)
    if scatter:
        assert feature_axis is None, "hist_agg=scatter excludes feature_axis"
        assert num_shards > 0, "hist_agg=scatter needs static num_shards"
        f_chunk = (f + num_shards - 1) // num_shards
        f_pad = f_chunk * num_shards
        my_off = (jax.lax.axis_index(psum_axis) * f_chunk).astype(jnp.int32)
        fmask_pad = jnp.pad(feature_mask, (0, f_pad - f))

    if feature_axis is not None:
        f_offset = (jax.lax.axis_index(feature_axis) * f).astype(jnp.int32)

    def psum(x):
        if not psum_axis:
            return x
        with jax.named_scope(spans.HIST_EXCHANGE):
            return jax.lax.psum(x, psum_axis)

    @jax.named_scope(spans.GAIN_SCAN)
    def best_of(hist, cnt, sg, sh):
        """find_best_split + cross-shard reduction.  In voting/scatter mode
        `hist` is shard-LOCAL; cnt/sg/sh are always global leaf stats."""
        if scatter:
            with jax.named_scope(spans.HIST_EXCHANGE):
                histp = jnp.pad(hist, ((0, f_pad - f), (0, 0), (0, 0)))
                mine = jax.lax.psum_scatter(histp, psum_axis,
                                            scatter_dimension=0, tiled=True)
            fm = jax.lax.dynamic_slice_in_dim(fmask_pad, my_off, f_chunk)
            s = find_best_split(mine, cnt, sg, sh, fm, params)
            return _reduce_best_over_features(s, my_off, psum_axis)
        if voting:
            # local scoring pass over local totals
            lsg = jnp.sum(hist[0, :, 0])
            lsh = jnp.sum(hist[0, :, 1])
            lcnt = jnp.round(jnp.sum(hist[0, :, 2])).astype(jnp.int32)
            gains_f, _ = per_feature_best(hist, lcnt, lsg, lsh,
                                          feature_mask, params)
            k = min(voting_top_k, f)
            topv, topi = jax.lax.top_k(gains_f, k)
            votes = jnp.zeros(f, dtype=jnp.float32).at[topi].add(
                jnp.where(topv > K_MIN_SCORE, 1.0, 0.0))
            votes = psum(votes)
            # global top-2k by votes, ties to the smaller feature index
            # (unique integer-valued keys keep top_k deterministic)
            k2 = min(2 * voting_top_k, f)
            key = votes * (f + 1) - jnp.arange(f, dtype=jnp.float32)
            cand = jax.lax.top_k(key, k2)[1].astype(jnp.int32)
            cand_hist = psum(hist[cand])
            s = find_best_split(cand_hist, cnt, sg, sh,
                                feature_mask[cand], params)
            return s._replace(feature=cand[s.feature])
        s = find_best_split(hist, cnt, sg, sh, feature_mask, params)
        if feature_axis is not None:
            s = _reduce_best_over_features(s, f_offset, feature_axis)
        return s

    def feature_go_right(feature, threshold):
        """Per-row `bin > threshold` for a GLOBAL feature index.

        Serial/data-parallel: read the local bin row.  Feature-parallel:
        the OWNER shard evaluates the comparison and broadcasts a packed
        [N/8] u8 bitmask over the feature axis (one shard contributes,
        psum replicates) — the reference's premise that every machine
        holds all rows (feature_parallel_tree_learner.cpp:45-78) means
        only the DECISION must move, and the reference moves 2 SplitInfos
        per split for the same reason.  Shipping the packed decision
        instead of the raw [N] i32 bin row (VERDICT r3 weak #4) cuts the
        per-split feature-axis traffic 32x (~4 MB -> ~128 KB at 1M
        rows)."""
        if feature_axis is None:
            return bins_t[feature].astype(jnp.int32) > threshold
        local = feature - f_offset
        owner = (local >= 0) & (local < f)
        row = jnp.where(owner,
                        bins_t[jnp.clip(local, 0, f - 1)].astype(jnp.int32),
                        0)
        gr = owner & (row > threshold)
        n8 = -(-n // 8) * 8
        bits = jnp.pad(gr, (0, n8 - n)).reshape(-1, 8)
        weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
        packed = jnp.sum(bits * weights[None, :], axis=1,
                         dtype=jnp.int32).astype(jnp.uint8)
        with jax.named_scope(spans.HIST_EXCHANGE):
            packed = jax.lax.psum(packed, feature_axis)
        unpacked = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)) \
            & jnp.uint8(1)
        return unpacked.reshape(-1)[:n].astype(bool)

    # voting/scatter keep histograms shard-local (cross-shard reduction
    # happens inside best_of); plain psum all-reduces the full tensor
    hist_psum = (lambda x: x) if (voting or scatter) else psum

    ranged_on = (ranged and hist_impl == "pallas"
                 and feature_axis is None)
    no_blocks = jnp.zeros(4, dtype=jnp.int32)   # hist_leaf's count elsewhere
    if hist_impl == "pallas":
        from .hist_pallas import (PALLAS_ROW_BLOCK, PART_BLOCKS, fold_bag_bit,
                                  fold_leaf_mask, leaf_histogram_blocklist,
                                  leaf_histogram_masked, leaf_of,
                                  leaf_partition_blocklist, make_gh2,
                                  part_groups)
        with jax.named_scope(spans.HIST_SWEEP):
            gh2 = make_gh2(grad, hess)
        # TPU runs the compiled kernel; CPU (tests) uses interpret mode
        interpret = jax.default_backend() == "cpu"
    if ranged_on:
        # Block-list sweeps (VERDICT r2 #1): per split, sweep ONLY the
        # row blocks that contain the target leaf's rows; skipped blocks
        # contribute exact +0.0f in the full sweep, so the result is
        # BIT-identical to it for the same row order.  Pays off when rows
        # are leaf-clustered (the ordered-partition mode in models/gbdt.py
        # re-sorts rows by the previous tree's leaves every few trees).
        # The kernel's grid ends at the leaf's last occupied block (a
        # run-time bound), so a sweep costs its own blocks and one
        # compiled kernel serves every leaf size.
        # Which blocks a leaf occupies is STATE (GrowState.occ), not a
        # scan of the ids: the root's row is made once a tree, and the
        # partition pass of a split, which runs over the split leaf's
        # own blocks and writes the ids in place, says of each which
        # child has a row there.  Nothing in `step` touches all N rows.
        # The ids carry the bag as a high bit (fold_bag_bit, once a
        # tree): the sweep's target never equals an out-of-bag row.
        # Under tree_learner=data (psum_axis set) everything here is
        # shard-LOCAL — blocks, occupancy, block list, grid, re-sorts —
        # except the histogram reduction the other impls share
        # (hist_psum): each shard's kernel runs to its own count.
        nblocks = n // PALLAS_ROW_BLOCK

        @jax.named_scope(spans.BLOCK_LIST)
        def block_list(occ):
            """(occupied block ids first and ascending, their count): the
            stable argsort of the complement keeps file order => the full
            sweep's association."""
            return (jnp.argsort(jnp.where(occ, 0, 1).astype(jnp.int32),
                                stable=True).astype(jnp.int32),
                    jnp.sum(occ).astype(jnp.int32))

        def hist_leaf(leaf_id, target, occ, scope=spans.HIST_SWEEP):
            """occ [nblocks]: the blocks that hold an in-bag row of it."""
            blist, n_occ = block_list(occ)
            with jax.named_scope(scope):
                h = leaf_histogram_blocklist(
                    bins_t, gh2, leaf_id, target, blist, n_occ,
                    max_bin=max_bin, interpret=interpret).astype(dtype)
            # (occupied blocks, row steps the kernel ran): an empty leaf
            # still runs one step; the rows are the caller's to add
            return hist_psum(h), jnp.stack([n_occ, jnp.maximum(n_occ, 1),
                                            jnp.int32(0), jnp.int32(0)])

        groups = part_groups(nblocks)
        # row blocks of each group: PART_BLOCKS, the last what is left
        group_width = jnp.diff(jnp.minimum(
            jnp.arange(groups + 1) * PART_BLOCKS, nblocks)).astype(jnp.int32)

        def partition(st, bl, right, feature, threshold, keep, wl, wr):
            """The split's ONE pass over the groups of row blocks the
            split leaf lies in: the new ids, the table with the children's
            rows, and the blocks visited."""
            was = st.occ[bl]
            with jax.named_scope(spans.BLOCK_LIST):
                held = jnp.pad(was, (0, groups * PART_BLOCKS - nblocks)) \
                    .reshape(groups, PART_BLOCKS).any(axis=1)
            glist, n_held = block_list(held)
            leaf_id, left, right_ = leaf_partition_blocklist(
                bins_t, st.leaf_id, glist, n_held, bl, right, feature,
                threshold, keep, interpret=interpret)
            with jax.named_scope(spans.BLOCK_LIST):
                # a block the pass did not visit held no row of the leaf
                # (the tables take whole rows: no layout to choose)
                occ = st.occ.at[wl].set(was & left[0]) \
                            .at[wr].set(was & right_[0])
                occ_bag = st.occ_bag.at[wl].set(was & left[1]) \
                                    .at[wr].set(was & right_[1])
                visited = jnp.sum(jnp.where(held & keep, group_width, 0))
            return leaf_id, occ, occ_bag, jnp.stack(
                [0, 0, visited, 0]).astype(jnp.int32)

        with jax.named_scope(spans.BLOCK_LIST):
            leaf_id0 = fold_bag_bit(bag_mask)
            occ0 = jnp.zeros((max_leaves + 1, nblocks), dtype=bool)
            occ_bag0 = occ0.at[0].set(
                bag_mask.reshape(nblocks, PALLAS_ROW_BLOCK).any(axis=1))
            occ0 = occ0.at[0].set(True)
    else:
        leaf_id0 = jnp.zeros(n, dtype=jnp.int32)
        occ0 = occ_bag0 = jnp.zeros((max_leaves + 1, 0), dtype=bool)
        if hist_impl == "pallas":
            def hist_leaf(leaf_id, target, occ, scope=spans.HIST_SWEEP):
                with jax.named_scope(scope):
                    leaf_eff = fold_leaf_mask(leaf_id, bag_mask)
                    h = leaf_histogram_masked(
                        bins_t, gh2, leaf_eff, target, max_bin=max_bin,
                        interpret=interpret).astype(dtype)
                return hist_psum(h), no_blocks
        else:
            def hist_leaf(leaf_id, target, occ, scope=spans.HIST_SWEEP):
                with jax.named_scope(scope):
                    gv = make_gvals(grad, hess,
                                    (leaf_id == target) & bag_mask, dtype)
                    h = leaf_histogram(bins_t, gv, max_bin=max_bin,
                                       row_chunk=row_chunk)
                return hist_psum(h), no_blocks

        def partition(st, bl, right, feature, threshold, keep, wl, wr):
            """One vectorized compare over every row (replaces
            DataPartition::Split, data_partition.hpp:84-132)."""
            go_right = (keep & (st.leaf_id == bl)
                        & feature_go_right(feature, threshold))
            return (jnp.where(go_right, right, st.leaf_id), st.occ,
                    st.occ_bag, no_blocks)

    def packed_best(best, depth):
        """The depth gate (no split at max_depth) and the packing."""
        if max_depth > 0:
            best = best._replace(
                gain=jnp.where(depth >= max_depth, K_MIN_SCORE, best.gain))
        return _pack_best(best, dtype)

    # ---- root ----
    root_hist, root_swept = hist_leaf(leaf_id0, jnp.int32(0), occ_bag0[0],
                                      scope=spans.HIST_ROOT)
    # every row lands in exactly one bin of feature 0, so its histogram sums
    # are the root totals (LeafSplits::Init root sumup, leaf_splits.hpp:36-117);
    # in voting mode the hist is local, so all-reduce the three scalars
    # (the reference's root Allreduce, data_parallel_tree_learner.cpp:94-122)
    with jax.named_scope(spans.HIST_ROOT):
        root_g = jnp.sum(root_hist[0, :, 0])
        root_h = jnp.sum(root_hist[0, :, 1])
        root_c = jnp.sum(root_hist[0, :, 2])
        if voting or scatter:
            root_g, root_h, root_c = (psum(root_g), psum(root_h),
                                      psum(root_c))
        root_cnt = jnp.round(root_c).astype(jnp.int32)

    def targeted(rows):
        """The swept counter of a sweep over a leaf of `rows` in-bag rows
        (a leaf count of the tree: global already, so never summed over
        shards); 0 off the block list, as the blocks are."""
        return no_blocks.at[3].set(rows) if ranged_on else no_blocks

    tree = _empty_tree(max_leaves, dtype)
    tree = tree._replace(leaf_count=tree.leaf_count.at[0].set(root_cnt))
    best_f0, best_i0 = _empty_best_packed(max_leaves, dtype)
    with jax.named_scope(spans.GAIN_SCAN):
        rbf, rbi = packed_best(best_of(root_hist, root_cnt, root_g, root_h),
                               jnp.int32(1))
        best_f0 = best_f0.at[0].set(rbf)
        best_i0 = best_i0.at[0].set(rbi)

    pooled = 0 < hist_slots < max_leaves + 1
    K = hist_slots if pooled else max_leaves
    if pooled:
        leaf_slot0 = jnp.full(max_leaves + 1, -1, dtype=jnp.int32).at[0].set(0)
        slot_leaf0 = jnp.full(K + 1, -1, dtype=jnp.int32).at[0].set(0)
        slot_used0 = jnp.full(K + 1, -1, dtype=jnp.int32).at[0].set(0)
    else:   # zero-size placeholders keep the scan-state pytree uniform
        leaf_slot0 = slot_leaf0 = slot_used0 = jnp.zeros(0, dtype=jnp.int32)

    with jax.named_scope(spans.HIST_POOL):
        hist0 = jnp.zeros((K + 1, f, max_bin, 3), dtype=dtype) \
                   .at[0].set(root_hist)
    state = GrowState(
        tree=tree,
        leaf_id=leaf_id0, occ=occ0, occ_bag=occ_bag0, hist=hist0,
        leaf_sum_g=jnp.zeros(max_leaves + 1, dtype=dtype).at[0].set(root_g),
        leaf_sum_h=jnp.zeros(max_leaves + 1, dtype=dtype).at[0].set(root_h),
        best_f=best_f0, best_i=best_i0,
        swept=root_swept + targeted(root_cnt),
        leaf_slot=leaf_slot0, slot_leaf=slot_leaf0, slot_used=slot_used0,
    )

    # Fixed-trip scan instead of lax.while_loop: a while_loop's per-
    # iteration continuation check serializes against the body's full
    # critical path (measured ~ms/step before round 6, ~8x the body
    # itself; not re-measured on the current code).  The scan always
    # runs max_leaves-1 steps; once growth
    # stops (no positive gain / leaf budget reached) every update is
    # redirected to the DUMMY slot (index max_leaves for leaves, the last
    # node slot for nodes) so the real state passes through untouched —
    # preserving the reference's early-stop semantics
    # (serial_tree_learner.cpp:121-129) without a whole-state select.
    def step(st: GrowState, t):
        tree = st.tree
        with jax.named_scope(spans.TREE_UPDATE):
            # argmax over leaves; first max ⇒ smaller leaf index, matching
            # ArrayArgs::ArgMax over best_split_per_leaf_
            # (serial_tree_learner.cpp:121)
            bl = jnp.argmax(
                st.best_f[:max_leaves, BF_GAIN]).astype(jnp.int32)
            sf = st.best_f[bl]
            si = st.best_i[bl]
            s_gain = sf[BF_GAIN]
            s_feature = si[BI_FEAT]
            s_threshold = si[BI_THR]
            keep = (tree.num_leaves < max_leaves) & (s_gain > 0.0)

            node = tree.num_leaves - 1
            right = tree.num_leaves           # new leaf index
            # dummy-slot redirection: all writes of an inactive step land in
            # scratch entries that the output never reads
            wl = jnp.where(keep, bl, max_leaves)          # leaf-array writes
            wr = jnp.where(keep, right, max_leaves)
            wn = jnp.where(keep, node, max_leaves - 1)    # node-array writes
            parent = tree.leaf_parent[bl]

            # --- Tree::Split (reference src/io/tree.cpp:42-77) ---
            pidx = jnp.where(keep & (parent >= 0), parent, max_leaves - 1)
            lc = tree.left_child
            lc = lc.at[pidx].set(jnp.where(
                keep & (parent >= 0) & (lc[pidx] == ~bl), node, lc[pidx]))
            rc = tree.right_child
            rc = rc.at[pidx].set(jnp.where(
                keep & (parent >= 0) & (rc[pidx] == ~bl), node, rc[pidx]))
            lc = lc.at[wn].set(jnp.where(keep, ~bl, lc[wn]))
            rc = rc.at[wn].set(jnp.where(keep, ~right, rc[wn]))

            new_tree = tree._replace(
                split_feature=tree.split_feature.at[wn].set(
                    jnp.where(keep, s_feature, tree.split_feature[wn])),
                threshold_bin=tree.threshold_bin.at[wn].set(
                    jnp.where(keep, s_threshold, tree.threshold_bin[wn])),
                split_gain=tree.split_gain.at[wn].set(
                    jnp.where(keep, s_gain, tree.split_gain[wn])),
                left_child=lc, right_child=rc,
                leaf_parent=tree.leaf_parent.at[wl].set(node)
                                            .at[wr].set(node),
                leaf_value=tree.leaf_value.at[wl].set(sf[BF_LOUT])
                                          .at[wr].set(sf[BF_ROUT]),
                internal_value=tree.internal_value.at[wn].set(
                    jnp.where(keep, tree.leaf_value[bl],
                              tree.internal_value[wn])),
                leaf_depth=tree.leaf_depth
                    .at[wr].set(tree.leaf_depth[bl] + 1)
                    .at[wl].add(1),
                leaf_count=tree.leaf_count.at[wl].set(si[BI_LCNT])
                                          .at[wr].set(si[BI_RCNT]),
                num_leaves=tree.num_leaves + keep.astype(jnp.int32),
            )

        # --- histograms: smaller child scanned, larger by subtraction ---
        with jax.named_scope(spans.HIST_POOL):
            left_is_smaller = si[BI_LCNT] <= si[BI_RCNT]
            small_leaf = jnp.where(left_is_smaller, bl, right)
            if pooled:
                # parent histogram from its pool slot, or a full recompute
                # when it was LRU-evicted (the reference recomputes evicted
                # leaves the same way, feature_histogram.hpp:275-398 +
                # serial_tree_learner.cpp BeforeFindBestSplit); it reads
                # the ids BEFORE the partition pass writes them in place
                parent_slot = st.leaf_slot[bl]
                parent_hist, reswept = jax.lax.cond(
                    parent_slot >= 0,
                    lambda: (st.hist[jnp.clip(parent_slot, 0, K - 1)],
                             no_blocks),
                    lambda: hist_leaf(st.leaf_id, bl, st.occ_bag[bl]))
                reswept = reswept + targeted(jnp.where(
                    parent_slot >= 0, 0, tree.leaf_count[bl]))
            else:
                parent_hist, reswept = st.hist[bl], no_blocks

        # --- partition: the split leaf's rows over the threshold take the
        # new leaf's index; no data movement ---
        with jax.named_scope(spans.PARTITION):
            leaf_id, occ, occ_bag, parted = partition(
                st, bl, right, s_feature, s_threshold, keep, wl, wr)

        with jax.named_scope(spans.HIST_SWEEP):
            # (a step after growth has stopped sweeps nothing: its
            # histograms would land in the dummy slot)
            small_hist, swept = hist_leaf(leaf_id, small_leaf,
                                          occ_bag[small_leaf] & keep)
            swept = swept + targeted(jnp.where(
                keep, jnp.minimum(si[BI_LCNT], si[BI_RCNT]), 0))
        with jax.named_scope(spans.HIST_POOL):
            large_hist = parent_hist - small_hist
            left_hist = jnp.where(left_is_smaller, small_hist, large_hist)
            right_hist = jnp.where(left_is_smaller, large_hist, small_hist)
            if pooled:
                # slot allocation: the left child (which keeps leaf index bl)
                # reuses the parent's slot when cached, else takes the LRU
                # slot; the right child takes the LRU slot among the rest
                slot_l = jnp.where(
                    parent_slot >= 0, parent_slot,
                    jnp.argmin(st.slot_used[:K]).astype(jnp.int32))
                used_tmp = st.slot_used.at[
                    jnp.clip(slot_l, 0, K - 1)].set(t)
                slot_r = jnp.argmin(used_tmp[:K]).astype(jnp.int32)
                wsl = jnp.where(keep, slot_l, K)      # dummy-slot redirection
                wsr = jnp.where(keep, slot_r, K)
                hist = st.hist.at[wsl].set(left_hist) \
                              .at[wsr].set(right_hist)
                # drop the evicted occupants' mappings, then map the children
                # (ordering matters: when the parent's slot is reused its
                # occupant IS bl — cleared first, remapped after)
                evict_l = st.slot_leaf[jnp.clip(slot_l, 0, K - 1)]
                evict_r = st.slot_leaf[jnp.clip(slot_r, 0, K - 1)]
                leaf_slot = (
                    st.leaf_slot
                    .at[jnp.where(keep & (evict_l >= 0), evict_l,
                                  max_leaves)].set(-1)
                    .at[jnp.where(keep & (evict_r >= 0), evict_r,
                                  max_leaves)].set(-1)
                    .at[wl].set(jnp.where(keep, slot_l, -1))
                    .at[wr].set(jnp.where(keep, slot_r, -1)))
                slot_leaf = st.slot_leaf.at[wsl].set(bl).at[wsr].set(right)
                slot_used = st.slot_used.at[wsl].set(t).at[wsr].set(t)
            else:
                hist = st.hist.at[wl].set(left_hist).at[wr].set(right_hist)
                leaf_slot, slot_leaf, slot_used = (
                    st.leaf_slot, st.slot_leaf, st.slot_used)

            leaf_sum_g = st.leaf_sum_g.at[wl].set(sf[BF_LG]) \
                                      .at[wr].set(sf[BF_RG])
            leaf_sum_h = st.leaf_sum_h.at[wl].set(sf[BF_LH]) \
                                      .at[wr].set(sf[BF_RH])

        # --- best splits for the two children ---
        with jax.named_scope(spans.GAIN_SCAN):
            child_depth = new_tree.leaf_depth[bl]
            lbest = best_of(left_hist, si[BI_LCNT], sf[BF_LG], sf[BF_LH])
            rbest = best_of(right_hist, si[BI_RCNT], sf[BF_RG], sf[BF_RH])
            lbf, lbi = packed_best(lbest, child_depth)
            rbf, rbi = packed_best(rbest, child_depth)
            best_f = st.best_f.at[wl].set(lbf).at[wr].set(rbf)
            best_i = st.best_i.at[wl].set(lbi).at[wr].set(rbi)

        return GrowState(tree=new_tree, leaf_id=leaf_id, occ=occ,
                         occ_bag=occ_bag, hist=hist,
                         leaf_sum_g=leaf_sum_g, leaf_sum_h=leaf_sum_h,
                         best_f=best_f, best_i=best_i,
                         swept=st.swept + reswept + parted + swept,
                         leaf_slot=leaf_slot, slot_leaf=slot_leaf,
                         slot_used=slot_used), None

    final, _ = jax.lax.scan(step, state,
                            jnp.arange(1, max_leaves, dtype=jnp.int32))
    swept = psum(final.swept[:3])
    with jax.named_scope(spans.PARTITION):
        leaf_id = leaf_of(final.leaf_id) if ranged_on else final.leaf_id
    return final.tree._replace(blocks_swept=swept[0], grid_rows=swept[1],
                               partition_blocks=swept[2],
                               rows_swept=final.swept[3]), leaf_id


@contract.traced_pure
def grow_tree_bagged(bins_t: jax.Array, grad: jax.Array, hess: jax.Array,
                     bag_mask: jax.Array, feature_mask: jax.Array, *,
                     bag_rows: int = 0, **grow_kw):
    """Bag-compacted grow_tree entry (the fused-path fast path when
    bagging leaves a fixed fraction of rows out of every tree).

    Rows arrive pre-arranged in-bag-first (models/gbdt.py
    _arrange_for_bag): every in-bag row lives in the static window
    [0, bag_rows), so histogram sweeps, the leaf_id partition compares
    and the whole grow scan run over bag_rows rows instead of N.
    `bag_rows` is a PYTHON int (static under jit — graftlint GL011
    guards this), so the window slice shapes are stable across
    re-bagging epochs and the executable never retraces.

    Out-of-bag tail rows no longer ride leaf_id through the scan: their
    leaf assignment comes from a replay of the finished tree's splits
    over the complement (ops/predict.py replay_leaf_binned: one pass over
    a bin row and the ids a split, 49 ms a tree at 13.6M rows where the
    gather descent took 2.50 s), as the reference updates scores by two
    paths (partition fast path + OOB traversal,
    src/boosting/gbdt.cpp:162-167).  The returned leaf_id still covers
    ALL rows (window ids from the scan, tail ids from the replay; the
    two agree bit-for-bit with a full-row scan, which routes rows by
    the same compares).

    Under shard_map (psum_axis set) everything added here is
    shard-local — the descent has no collectives — so per-shard bag
    compaction preserves the psum pairing invariants untouched.

    bag_rows <= 0 or >= N falls through to the plain masked full sweep
    (the bit-parity oracle)."""
    n = bins_t.shape[1]
    if bag_rows <= 0 or bag_rows >= n:
        return grow_tree(bins_t, grad, hess, bag_mask, feature_mask,
                         **grow_kw)
    tree, leaf_w = grow_tree(bins_t[:, :bag_rows], grad[:bag_rows],
                             hess[:bag_rows], bag_mask[:bag_rows],
                             feature_mask, **grow_kw)
    with jax.named_scope(spans.OOB_DESCENT):
        # (an unsplit stump replays no split: its rows stay at leaf 0, whose
        # value drives the score update, as the scan's leaf_id has them)
        oob = replay_leaf_binned(tree.split_feature, tree.threshold_bin,
                                 tree.left_child, tree.num_leaves,
                                 bins_t[:, bag_rows:])
        # the barrier keeps the compiler from sinking the concatenation
        # into the score update's look-up, which it then made in pieces
        # (0.18 s a tree at 68M rows where the whole look-up takes 0.003,
        # and 10 s more to compile; PERF.md section 6, PR 33)
        return tree, jax.lax.optimization_barrier(
            jnp.concatenate([leaf_w, oob.astype(leaf_w.dtype)]))
