"""Pallas TPU histogram kernel — the fast path for the #1 hot loop.

The XLA formulation (ops/histogram.py) materializes per-feature one-hot
matrices in HBM (~N*B bytes per feature per split), which dominates at
scale.  This kernel uses a radix decomposition bin = hi*32 + lo and packs
MM_FEATS=4 features into ONE block-diagonal MXU matmul (a grid step
covers _feat_block(F) <= FEAT_BLOCK_CAP features, a quarter as many
matmuls, the block chosen from F: 39 features are ONE block of 40):

    lhs[(f, c, hi), r] = gh3[c, r] * (bins_hi[f, r] == hi)   [96, blk]
    rhs[r, (f, lo)]    = (bins_lo[f, r] == lo)               [blk, 128]
    part = lhs @ rhs                                         [96, 128]

so hist[f, hi*32+lo, c] is the f-diagonal of the [4x4 blocks] product.
The off-diagonal (f != f') blocks are wasted FLOPs, but the [96,128]x[blk]
shape keeps the MXU at near-full tile utilization — ~5x faster end-to-end
than one [32, blk] x [blk, 32] matmul per feature, whose 32-wide tiles run
the MXU at 1/16 of peak.

Inputs are kept slim because HBM streaming dominates: bins [F, N] uint8,
gh2 [2, N] (grad, hess; built once per tree), and ONE leaf_eff [N]
int32 with the bagging mask pre-folded (out-of-bag rows get -1, which can
never equal a target leaf).  The (leaf_eff == target) mask is computed
in-kernel, so per-split traffic is bins + gh2 + leaf_eff only — no [N]
per-split gvals materialization.  The bin matrix is read as it lies in
HBM: F need not divide the feature block, the last block then runs past
the array (39 features: row 39 of a block of 40), and no wrapper copies
the matrix to whole blocks.  What the rows past the array hold is
unspecified and cannot reach a result (_feat_grid says why); the
compiled kernels agree to the bit with the same kernels on a matrix
padded by the caller, with zeros or with random bytes (TPU v5e, F = 13,
28, 39, 47: PERF.md, PR 26), and a sweep at any feature block with the
sweep at blocks of 16 (F = 28, 39, 220, 2000: PERF.md, PR 32).

What the chip does (TPU v5e, jax 0.9.0, measured in PR 21 — PERF.md
Findings): the f32 dot runs at Mosaic's default matmul precision, which
rounds the grad/hess operand to bfloat16 before the product, so COMPILED
histograms keep exact counts and carry up to 2**-8 relative operand
error in grad/hess — as do the XLA one-hot histograms of
ops/histogram.py, for the same reason.  Only the interpreted kernels
(CPU) accumulate true f32 products.

Two wrappers, one per sweep mode: leaf_histogram_masked sweeps every row
block; leaf_histogram_blocklist sweeps the blocks a list names (the
ordered-partition mode of ops/grow.py), where leaf_partition_blocklist,
at the end of this file, is the split's pass over the leaf ids.  The gain scan is a separate XLA
pass over the [F, B, 3] tensor (ops/split.find_best_split): 0.05% of a
tree at 68M x 39 (PERF.md, PR 28's ledger lines).

Equivalent to DenseBin::ConstructHistogram (reference
src/io/dense_bin.hpp:39-104) with the leaf/bag mask folded into the
accumulated values.  Supports max_bin <= 256.
"""

from __future__ import annotations

import functools

from ..utils.compile_cache import enable_compilation_cache

enable_compilation_cache()   # before any jit traces (was a package-import side effect)

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MM_FEATS = 4      # features per block-diagonal matmul
# A grid step's feature block is chosen from F (_feat_block) by what a
# step costs on the chip: a [96, 8192] x [8192, 128] matmul with its
# one-hot operands, and what a step costs besides (the leaf mask, gh3,
# the int32 bins, three block DMAs: gh2 and leaf_eff stream from HBM
# once per row block per GRID STEP).  Read from full sweeps of F = 28,
# 39, 220 and 2000 at feature blocks of 8 to 80: 20 readings, least
# squares, the worst 0.14 us off the line (TPU v5e; PERF.md, PR 32).
# Nanoseconds, whole numbers, so that equal costs ARE equal.
T_MM_NS = 1051
T_STEP_NS = 227
# The widest block a step may take (a multiple of 8: sublane tiling).
# Not what VMEM holds: Mosaic takes 256 features a step under the 16 MiB
# scoped default, since the unrolled matmuls share their operand
# buffers.  It is where the matmuls stop costing T_MM_NS: 1.06 us each
# at 80 features a step, 1.95-1.99 at 88, 96 and 104 and 1.90 at 112,
# at 64 and 100 MiB of vmem_limit_bytes too; as a fori_loop they cost
# 1.14-1.18 at every width (same chip, same PR).
FEAT_BLOCK_CAP = 80
N_HI = 8
N_LO = 32
N_COMP = 3    # grad, hess, count
M_ROWS = MM_FEATS * N_COMP * N_HI   # 96
N_COLS = MM_FEATS * N_LO            # 128
PALLAS_ROW_BLOCK = 8192   # rows per grid step; N must be a multiple —
#                           this is also the alignment of the
#                           bag-compacted sweep window (models/gbdt.py
#                           pads the static in-bag window to it), so the
#                           kernels never see a partial block

# Every pl.pallas_call below has an explicit name= that holds
# "leaf_histogram".  A Pallas custom call's HLO instruction, and so its
# event in a device trace, is named after the INNERMOST component of its
# name stack: the kernel's name= when it has one, else whatever
# jax.named_scope or jit wrapper encloses the call.  The benchmark's sweep
# reader sums the events named `%leaf_histogram*`, and the kernel body
# (name included) is part of the persistent compile cache's key where
# scopes, being metadata, are not (tests/test_spans.py; PERF.md, PR 25).


def _block_cost_ns(f: int, fb: int) -> int:
    """What ONE row block of an [F, N] matrix costs swept fb features a
    grid step: cdiv(F, fb) steps of fb / MM_FEATS matmuls each."""
    return -(-f // fb) * (fb // MM_FEATS * T_MM_NS + T_STEP_NS)


def _feat_block(f: int) -> int:
    """The multiple of 8 up to FEAT_BLOCK_CAP that sweeps a row block of
    F features cheapest, of equals the widest: 39 -> 40 (ten matmuls in
    one step, where three blocks of 16 ran twelve in three), 220 -> 56
    (the 56 matmuls of 14 blocks of 16, in four steps; 80 would run 60
    in three)."""
    return min(range(8, FEAT_BLOCK_CAP + 1, 8),
               key=lambda fb: (_block_cost_ns(f, fb), -fb))


def _feat_grid(f: int):
    """(fb, fpad, groups) of an [F, N] bin matrix: the feature block, F
    rounded up to it, and the grid's feature extent cdiv(F, fb).

    The kernels read the matrix IN PLACE: where fb does not divide F the
    last feature block runs past the array (39 features: row 39 of the
    block of 40; 220: rows 220-223 of the fourth block of 56), and no
    wrapper pads it — a pad here is a copy of the whole resident matrix
    at every split, which XLA does not hoist out of the grow scan (16%
    of a tree at 68M x 39: PERF.md, PR 26).  What the rows past the
    array hold is unspecified, and cannot reach a result:
    a bin is a byte that the kernel turns into one-hot operands by
    integer compares (hi == iota, lo == iota), so ANY byte gives zeros
    and ones, never a NaN; in the block-diagonal
    product a feature's rows and columns meet only its own diagonal
    block, the one _diag_hist_xla extracts, so the rows past F fill only
    their own slices of the [fpad, ...] output, which both wrappers cut
    off (hist[:f])."""
    fb = _feat_block(f)
    groups = (f + fb - 1) // fb
    return fb, groups * fb, groups


def row_step(f: int):
    """(feature groups, block-diagonal matmuls) ONE row step of a sweep
    over F features runs: the lgbm.flush stats feat_groups and
    block_matmuls, by which grid_rows counts grid steps and matmuls."""
    fb, _, groups = _feat_grid(f)
    return groups, groups * fb // MM_FEATS


def make_gh2(grad: jax.Array, hess: jax.Array) -> jax.Array:
    """[2, N] f32 (grad, hess) — per-tree constant rows."""
    return jnp.stack([grad.astype(jnp.float32), hess.astype(jnp.float32)])


def fold_leaf_mask(leaf_id: jax.Array, mask: jax.Array) -> jax.Array:
    """leaf_eff [N] i32: leaf_id where mask, else -1 (never a target)."""
    return jnp.where(mask, leaf_id.astype(jnp.int32), jnp.int32(-1))


def _hist_kernel(target_ref, bins_ref, gh_ref, leaf_ref, out_ref):
    """The radix matmul accumulation both sweeps share: the first row
    step initializes the block accumulators, later steps add."""
    target = target_ref[0]
    r = pl.program_id(1)
    feat_block, blk = bins_ref.shape

    def emit(init):
        mask = (leaf_ref[:] == target).astype(jnp.float32)
        gh3 = jnp.stack([gh_ref[0, :] * mask, gh_ref[1, :] * mask, mask])
        bins = bins_ref[...].astype(jnp.int32)                 # [fb, blk]
        hi = bins >> 5
        lo = bins & 31
        iota_hi = jax.lax.broadcasted_iota(jnp.int32, (N_HI, blk), 0)
        iota_lo = jax.lax.broadcasted_iota(jnp.int32, (N_LO, blk), 0)
        for m in range(feat_block // MM_FEATS):
            lhs_parts = []
            rhs_parts = []
            for f in range(m * MM_FEATS, (m + 1) * MM_FEATS):
                ohi = (hi[f][None, :] == iota_hi).astype(
                    jnp.float32)                               # [8, blk]
                lhs_parts.append((gh3[:, None, :] * ohi[None, :, :])
                                 .reshape(N_COMP * N_HI, blk))
                rhs_parts.append((lo[f][None, :] == iota_lo)
                                 .astype(jnp.float32))         # [32, blk]
            lhs = jnp.concatenate(lhs_parts, axis=0)           # [96, blk]
            # rhs stays lane-major [128, blk]: contracting BOTH operands
            # on the row (lane) dim avoids the [blk, 32] one-hot
            # transpose relayout
            rhs = jnp.concatenate(rhs_parts, axis=0)           # [128, blk]
            part = jax.lax.dot_general(
                lhs, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)            # [96, 128]
            if init:
                out_ref[0, m, :, :] = part
            else:
                out_ref[0, m, :, :] += part

    @pl.when(r == 0)
    def _init():
        emit(True)

    @pl.when(r != 0)
    def _acc():
        emit(False)


def _diag_hist_xla(out: jax.Array, fpad: int):
    """[groups, fb//4, 96, 128] accumulators -> [fpad, 256, 3] f32: the
    feature f == f' diagonal of the 4x4 block structure."""
    part = out.reshape(-1, MM_FEATS, N_COMP, N_HI, MM_FEATS, N_LO)
    diag = jnp.einsum("gfchfl->gfchl", part)
    return diag.transpose(0, 1, 3, 4, 2).reshape(fpad, N_HI * N_LO,
                                                 N_COMP)


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "row_block", "interpret"))
def leaf_histogram_masked(bins_t: jax.Array, gh2: jax.Array,
                          leaf_eff: jax.Array, target_leaf, *,
                          max_bin: int,
                          row_block: int = PALLAS_ROW_BLOCK,
                          interpret: bool = False) -> jax.Array:
    """Histogram over rows with leaf_eff == target_leaf.

    bins_t [F, N] uint8; gh2 [2, N] f32 (see make_gh2) — built ONCE per
    tree; leaf_eff [N] i32 with bagging folded in (see fold_leaf_mask).
    Returns hist [F, max_bin, 3] f32 with components (grad, hess, count).
    """
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    fb, fpad, groups = _feat_grid(f)
    nblocks = n // row_block
    target = jnp.asarray(target_leaf, dtype=jnp.int32).reshape(1)

    out = pl.pallas_call(
        _hist_kernel,
        grid=(groups, nblocks),   # row dim minor: out block stays in VMEM
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((fb, row_block), lambda i, r: (i, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, row_block), lambda i, r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((row_block,), lambda i, r: (r,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, fb // MM_FEATS, M_ROWS, N_COLS),
                               lambda i, r: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (groups, fb // MM_FEATS, M_ROWS, N_COLS), jnp.float32),
        interpret=interpret,
        name="leaf_histogram_masked",
    )(target, bins_t, gh2, leaf_eff)
    # rows are (f, c, hi), cols are (f', lo); feature f's histogram is the
    # f == f' diagonal of the 4x4 block structure
    return _diag_hist_xla(out, fpad)[:f, :max_bin, :]


def _hist_kernel_blocklist(target_ref, blist_ref, *refs):
    """target_ref and blist_ref are the scalar-prefetch operands (SMEM);
    blist_ref is consumed by the index maps."""
    _hist_kernel(target_ref, *refs)


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "row_block", "interpret"))
def leaf_histogram_blocklist(bins_t: jax.Array, gh2: jax.Array,
                             leaf_eff: jax.Array, target_leaf,
                             block_list: jax.Array, n_active, *,
                             max_bin: int,
                             row_block: int = PALLAS_ROW_BLOCK,
                             interpret: bool = False) -> jax.Array:
    """leaf_histogram_masked restricted to the row blocks named by
    block_list[:n_active] (any order; ascending preserves the full
    sweep's accumulation association, making the result BIT-identical to
    it — skipped blocks contribute exact +0.0f).  Correct whenever every
    row with leaf_eff == target_leaf lies in a listed block; rows of
    other leaves in listed blocks are masked as usual.

    The grid's row extent is n_active itself, read at RUN time (a traced
    int32 grid dimension): one compiled kernel whose cost is the leaf's
    own block count, no worst-case grid and no step that finds nothing
    to do (928 of a period's 990 sweeps ran 8,342 row steps whatever the
    leaf held: PERF.md, PR 30).  An empty list (n_active 0) runs one
    step over block_list[0], which the leaf mask zeroes.
    """
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    fb, fpad, groups = _feat_grid(f)
    nblocks = n // row_block
    target = jnp.asarray(target_leaf, dtype=jnp.int32).reshape(1)
    blist = jnp.clip(block_list.astype(jnp.int32), 0, nblocks - 1)
    rows = jnp.clip(jnp.asarray(n_active, jnp.int32), 1, nblocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(groups, rows),   # row dim minor: out block stays in VMEM
        in_specs=[
            pl.BlockSpec((fb, row_block), lambda i, r, t, bl: (i, bl[r])),
            pl.BlockSpec((2, row_block), lambda i, r, t, bl: (0, bl[r])),
            pl.BlockSpec((row_block,), lambda i, r, t, bl: (bl[r],)),
        ],
        out_specs=pl.BlockSpec((1, fb // MM_FEATS, M_ROWS, N_COLS),
                               lambda i, r, t, bl: (i, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _hist_kernel_blocklist,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (groups, fb // MM_FEATS, M_ROWS, N_COLS), jnp.float32),
        interpret=interpret,
        name="leaf_histogram_blocklist",
    )(target, blist, bins_t, gh2, leaf_eff)
    return _diag_hist_xla(out, fpad)[:f, :max_bin, :]


# ---- the partition pass of the ordered mode (ops/grow.py step) ----
# In the block-list mode the leaf ids carry the bag: an out-of-bag row's
# id has OOB_BIT set, folded ONCE a tree (fold_bag_bit), so such a row
# never equals a sweep's target, still follows its leaf through the
# splits, and its leaf is the bits below (leaf_of).
OOB_BIT = 1 << 30
PART_FEATS = 8    # bin rows a partition step reads: one sublane tile
# Row blocks a partition step covers.  A step costs 0.33 us for its DMAs'
# issue and wait whatever it moves and 0.2 us a block for the work on
# its rows (TPU v5e: PERF.md, PR 34), and a leaf's blocks lie in runs
# (its rows are spread thin through the ranges of an older tree's
# leaves), so the pass walks the leaf's GROUPS of PART_BLOCKS blocks.
PART_BLOCKS = 4
_LANES = 128
_RIGHT = 16       # a flag word counts rows that stay below this bit


def fold_bag_bit(mask: jax.Array) -> jax.Array:
    """[N] i32 ids of a tree's root: 0 where mask, OOB_BIT elsewhere."""
    return jnp.where(mask, jnp.int32(0), jnp.int32(OOB_BIT))


def leaf_of(leaf_id: jax.Array) -> jax.Array:
    """The leaf of every row of ids that carry the bag (fold_bag_bit)."""
    return leaf_id & jnp.int32(OOB_BIT - 1)


def part_groups(nblocks: int, part_blocks: int = PART_BLOCKS) -> int:
    """Groups of part_blocks row blocks that cover nblocks (the last may
    run past the array)."""
    return -(-nblocks // part_blocks)


def _partition_kernel(glist_ref, split_ref, bins_ref, ids_ref, out_ref,
                      flags_ref, row_ref, *, block_rows):
    """glist_ref and split_ref are the scalar-prefetch operands (SMEM):
    the list the index maps read, and (split leaf or -1, new leaf - split
    leaf, feature, threshold).  Everything is [1, rows]: ids and bin row
    lie along the lanes, as the sweep kernel reads them."""
    i32 = jnp.int32     # (spelled out: python ints are int64 under x64)
    rows = ids_ref.shape[0]
    ids = ids_ref[:].reshape(1, rows)
    # the feature's row of the tile, by a scalar branch: the store lays
    # it out the same whichever row it was (as a `lax.switch` result the
    # later rows cost twice the first: PERF.md, PR 34)
    which = jax.lax.rem(split_ref[2], i32(PART_FEATS))
    for k in range(PART_FEATS):
        @pl.when(which == k)
        def _(k=k):
            row_ref[...] = bins_ref[k:k + 1, :].astype(i32)
    mine = leaf_of(ids) == split_ref[0]
    go_right = mine & (row_ref[...] > split_ref[3])
    # the new leaf's index under the row's own bag bit
    out_ref[:] = jnp.where(go_right, ids + split_ref[1], ids).reshape(rows)
    # per block: rows that stay, and from bit _RIGHT up rows that go; over
    # any row, then over in-bag rows.  The counts go where the caller
    # reads them as they lie: block b of the pass at [b // 128, b % 128]
    # of a table that stays in VMEM through the grid (a group's blocks
    # share a row; an output block a step cost a fourth DMA, and its
    # [group, block, lane] form cost the caller 0.2-0.5 ms a split to
    # read: PERF.md, PR 34).  What no step wrote is whatever was there.
    moved = jnp.where(mine, jnp.where(go_right, i32(1 << _RIGHT), i32(1)),
                      i32(0))
    part_blocks = rows // block_rows
    group = glist_ref[pl.program_id(0)]
    at_row = jax.lax.div(group, i32(_LANES // part_blocks))
    lane0 = jax.lax.rem(group, i32(_LANES // part_blocks)) * part_blocks
    lane = jax.lax.broadcasted_iota(i32, (1, _LANES), 1)
    for k, counts in enumerate((moved, jnp.where(ids < OOB_BIT, moved,
                                                 i32(0)))):
        held = flags_ref[k, pl.ds(at_row, 1), :]
        for j in range(part_blocks):
            held = jnp.where(lane == lane0 + j, jnp.sum(
                counts[:, j * block_rows:(j + 1) * block_rows], axis=1,
                keepdims=True, dtype=i32), held)
        flags_ref[k, pl.ds(at_row, 1), :] = held


@functools.partial(jax.jit, static_argnames=("row_block", "part_blocks",
                                             "interpret"))
def leaf_partition_blocklist(bins_t: jax.Array, leaf_id: jax.Array,
                             group_list: jax.Array, n_active, split_leaf,
                             new_leaf, feature, threshold, keep, *,
                             row_block: int = PALLAS_ROW_BLOCK,
                             part_blocks: int = PART_BLOCKS,
                             interpret: bool = False):
    """One split's partition over the groups of part_blocks row blocks
    group_list[:n_active]: rows of split_leaf whose bin of `feature` is
    over `threshold` move to new_leaf.  Returns (leaf_id, left, right):
    the ids, and [2, nblocks] bool each.

    leaf_id [N] i32 carries the bag (fold_bag_bit) and is written IN
    PLACE: a group the list does not name is neither read nor written.
    Correct whenever every row of split_leaf lies in a listed group.
    left[i, b] says of a row block b of a LISTED group whether the left
    child (the rows that stay) has a row there, any row (i = 0) or an
    in-bag row (i = 1), right[i, b] the same of the rows that go; of any
    other block they say nothing (the memory is whatever it was), so the
    caller masks them with the occupancy the list was made from.

    The grid's extent is n_active, read at run time as
    leaf_histogram_blocklist's is; a step reads its rows of the ids and
    the tile of PART_FEATS bin rows that holds `feature` (the index map
    picks it, the kernel the row).  keep False (the grow scan's steps
    after growth has stopped) or an empty list runs one step, over
    group_list[0], that moves no row.  Where part_blocks does not divide
    the blocks the last group runs past the arrays, as a feature block
    does (_feat_grid): what it reads there reaches no row and no flag
    that is returned.
    """
    f, n = bins_t.shape
    rows = part_blocks * row_block
    assert n % row_block == 0 and row_block % _LANES == 0, (n, row_block)
    assert _LANES % part_blocks == 0, part_blocks
    nblocks = n // row_block
    groups = part_groups(nblocks, part_blocks)
    table_rows = -(-groups * part_blocks // _LANES)
    glist = jnp.clip(group_list.astype(jnp.int32), 0, groups - 1)
    n_active = jnp.asarray(n_active, jnp.int32)
    keep = jnp.asarray(keep, jnp.bool_) & (n_active > 0)
    steps = jnp.clip(jnp.where(keep, n_active, 0), 1, groups)
    split_leaf = jnp.asarray(split_leaf, jnp.int32)
    split = jnp.stack([
        jnp.where(keep, split_leaf, -1),     # no leaf: nothing matches
        jnp.asarray(new_leaf, jnp.int32) - split_leaf,
        jnp.asarray(feature, jnp.int32), jnp.asarray(threshold, jnp.int32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((PART_FEATS, rows),
                         lambda r, gl, s: (jax.lax.div(
                             s[2], jnp.int32(PART_FEATS)), gl[r])),
            pl.BlockSpec((rows,), lambda r, gl, s: (gl[r],)),
        ],
        out_specs=[
            pl.BlockSpec((rows,), lambda r, gl, s: (gl[r],)),
            pl.BlockSpec((2, table_rows, _LANES), lambda r, gl, s: (0, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, rows), jnp.int32)],
    )
    ids, counts = pl.pallas_call(
        functools.partial(_partition_kernel, block_rows=row_block),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((2, table_rows, _LANES), jnp.int32)],
        # operand 3 (after the two prefetched) is the ids: output 0
        input_output_aliases={3: 0},
        interpret=interpret,
        name="leaf_partition_blocklist",
    )(glist, split, bins_t, leaf_id)
    counts = counts.reshape(2, table_rows * _LANES)[:, :nblocks]
    return ids, (counts & ((1 << _RIGHT) - 1)) != 0, (counts >> _RIGHT) != 0
