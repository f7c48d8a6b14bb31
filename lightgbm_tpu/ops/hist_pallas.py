"""Pallas TPU histogram kernel — the fast path for the #1 hot loop.

The XLA formulation (ops/histogram.py) materializes per-feature one-hot
matrices in HBM (~N*B bytes per feature per split), which dominates at
scale.  This kernel uses a radix decomposition bin = hi*32 + lo and packs
MM_FEATS=4 features into ONE block-diagonal MXU matmul (a grid step
covers _feat_block(F) <= MAX_FEAT_BLOCK features, several matmuls):

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
the array (39 features: rows 32-47 of 39), and no wrapper copies the
matrix to whole blocks.  What the rows past the array hold is
unspecified and cannot reach a result (_feat_grid says why); the
compiled kernels agree to the bit with the same kernels on a matrix
padded by the caller, with zeros or with random bytes (TPU v5e, F = 13,
28, 39, 47: PERF.md, PR 26).

Accumulator modes (`hist_acc`, round 16): "f32" is the default and the
parity configuration; "bf16" streams gh2 and builds the one-hot operands
in bfloat16 (halving their VMEM footprint and the gh2 HBM stream) with
f32 MXU accumulation; "i32" quantizes gh2 to int32 fixed point with a
per-tree scale bounded so no sum of N terms can overflow (make_gh2_acc),
accumulates EXACTLY in integers (order-independent), and dequantizes on
output — counts come out exact.  bf16/i32 round the inputs, so they are
opt-in behind the f32 parity gate (config.hist_acc; tests pin their
divergence envelopes).

What the chip does with them (TPU v5e, jax 0.9.0, measured in PR 21 —
PERF.md Findings): the "f32" dot runs at Mosaic's default matmul
precision, which rounds the grad/hess operand to bfloat16 before the
product, so COMPILED f32 histograms equal bf16-mode ones (counts stay
exact; grad/hess carry up to 2**-8 relative operand error) — as do the
XLA one-hot histograms of ops/histogram.py, for the same reason.  Only
the interpreted kernels (CPU) accumulate true f32 products.  "i32" is
refused by the Mosaic back end on v5e (no int32 matmul).

Fused histogram+gain kernels (round 16): the *_fused variants extend the
masked / ranged / blocklist sweeps so the LAST grid step, with the
feature block's accumulators still resident in VMEM, also runs the
best-split threshold scan in-register — the exact jnp ops of
`ops/split.per_feature_split_rows`, on the exact accumulator values the
two-op path would extract — for the swept (small) child AND its sibling
(parent - small, the subtraction trick: the parent streams in once) and
emits one [F, 8] best row per child.  A tiny XLA argmax
(`ops/split.find_best_split_fused`) finishes the reduction, so the
[F, B, 3] tensor is written once for the histogram-pool state and never
read back for scanning: the ~2 full-tensor scan passes per split that
dominated the two-op path's non-sweep time disappear.  Interpret-mode
results are bit-identical to the two-op oracle by construction (same
ops, same values, same order).

Equivalent to DenseBin::ConstructHistogram (reference
src/io/dense_bin.hpp:39-104) with the leaf/bag mask folded into the
accumulated values, and — fused — to the reference's
ConstructHistograms -> FindBestThreshold pass that never leaves the
feature-histogram buffer (SURVEY §7.3).  Supports max_bin <= 256.
"""

from __future__ import annotations

import functools

from ..utils.compile_cache import enable_compilation_cache

enable_compilation_cache()   # before any jit traces (was a package-import side effect)

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .split import PF_COLS, SplitParams, per_feature_split_rows

MAX_FEAT_BLOCK = 16   # features per grid step (gh2/leaf_eff stream from
                      # HBM once per row block per GRID STEP, so wide
                      # feature blocks amortize that traffic; sublane
                      # tiling wants a multiple of 8)
MM_FEATS = 4      # features per block-diagonal matmul
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

HIST_ACC_MODES = ("f32", "bf16", "i32")

# Every pl.pallas_call below has an explicit name= that holds
# "leaf_histogram".  A Pallas custom call's HLO instruction, and so its
# event in a device trace, is named after the INNERMOST component of its
# name stack: the kernel's name= when it has one, else whatever
# jax.named_scope or jit wrapper encloses the call.  The benchmark's sweep
# reader sums the events named `%leaf_histogram*`, and the kernel body
# (name included) is part of the persistent compile cache's key where
# scopes, being metadata, are not (tests/test_spans.py; PERF.md, PR 25).

# SMEM scalar layouts of the fused kernels: info (int32[8]) and
# stats (float32[8])
IF_TARGET, IF_START, IF_ACTIVE, IF_CNT_S, IF_CNT_L = range(5)
SF_SG_S, SF_SH_S, SF_SG_L, SF_SH_L, SF_INV = range(5)


def _feat_block(f: int) -> int:
    return min(MAX_FEAT_BLOCK, ((f + 7) // 8) * 8)


def _feat_grid(f: int):
    """(fb, fpad, groups) of an [F, N] bin matrix: the feature block, F
    rounded up to it, and the grid's feature extent cdiv(F, fb).

    The kernels read the matrix IN PLACE: where fb does not divide F the
    last feature block runs past the array (39 features: rows 32-47 of a
    39-row array), and no wrapper pads it — a pad here is a copy of the
    whole resident matrix at every split, which XLA hoists out of neither
    the ladder's switch nor the grow scan (16% of a tree at 68M x 39:
    PERF.md, PR 26).  What the rows past the array hold is unspecified,
    and cannot reach a result: a bin is a byte that _accumulate turns
    into one-hot operands by integer compares (hi == iota, lo == iota),
    so ANY byte gives zeros and ones, never a NaN; in the block-diagonal
    product a feature's rows and columns meet only its own diagonal
    block, the one _diag_hist_xla extracts, so the rows past F fill only
    their own slices of the [fpad, ...] output, which every wrapper cuts
    off (hist[:f], pfs[:f])."""
    fb = _feat_block(f)
    groups = (f + fb - 1) // fb
    return fb, groups * fb, groups


def _operand_dtype(hist_acc: str):
    """dtype of the in-kernel one-hot/gh operands per accumulator mode."""
    if hist_acc == "bf16":
        return jnp.bfloat16
    if hist_acc == "i32":
        return jnp.int32
    return jnp.float32


def _acc_dtype(hist_acc: str):
    """dtype the MXU partials accumulate in (the out buffer)."""
    return jnp.int32 if hist_acc == "i32" else jnp.float32


def make_gh2(grad: jax.Array, hess: jax.Array) -> jax.Array:
    """[2, N] f32 (grad, hess) — per-tree constant rows."""
    return jnp.stack([grad.astype(jnp.float32), hess.astype(jnp.float32)])


def make_gh2_acc(grad: jax.Array, hess: jax.Array, hist_acc: str = "f32"):
    """(gh2 [2, N], inv_scale) in the accumulator mode's streaming dtype.

    f32: the parity default (inv_scale None).  bf16: rounded to
    bfloat16 — half the gh2 stream and operand VMEM.  i32: fixed-point
    quantization with a per-tree scale chosen so |q| <= 2**30 / N —
    ANY sum of N quantized terms stays inside int32, so integer
    accumulation can never overflow regardless of block/grid
    association; inv_scale (traced f32) dequantizes the grad/hess
    components on output (counts are exact integers already).
    """
    if hist_acc == "bf16":
        return make_gh2(grad, hess).astype(jnp.bfloat16), None
    if hist_acc == "i32":
        gh2 = make_gh2(grad, hess)
        n = max(int(grad.shape[0]), 1)
        cap = jnp.float32((2.0 ** 30) / n)
        m = jnp.maximum(jnp.max(jnp.abs(gh2)), jnp.float32(1e-30))
        scale = cap / m
        q = jnp.round(gh2 * scale).astype(jnp.int32)
        return q, (jnp.float32(1.0) / scale).astype(jnp.float32)
    return make_gh2(grad, hess), None


def dequant_hist(hist: jax.Array, hist_acc: str, inv_scale) -> jax.Array:
    """[..., 3]-component histogram -> f32, dequantizing the grad/hess
    components in i32 mode (counts carry scale 1 and come out exact)."""
    if hist_acc != "i32":
        return hist
    vec = jnp.stack([inv_scale, inv_scale, jnp.float32(1.0)])
    return hist.astype(jnp.float32) * vec


def fold_leaf_mask(leaf_id: jax.Array, mask: jax.Array) -> jax.Array:
    """leaf_eff [N] i32: leaf_id where mask, else -1 (never a target)."""
    return jnp.where(mask, leaf_id.astype(jnp.int32), jnp.int32(-1))


def _accumulate(target, bins_ref, gh_ref, leaf_ref, out_ref, r, active,
                hist_acc):
    """The shared radix matmul accumulation of every kernel variant:
    r == 0 initializes the block accumulators, later ACTIVE steps add.
    Inactive steps (ranged/blocklist grids past n_active) skip their
    matmuls — their cost is grid bookkeeping only."""
    feat_block, blk = bins_ref.shape
    odt = _operand_dtype(hist_acc)
    adt = _acc_dtype(hist_acc)

    def emit(init):
        mask = (leaf_ref[:] == target).astype(odt)
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
                ohi = (hi[f][None, :] == iota_hi).astype(odt)  # [8, blk]
                lhs_parts.append((gh3[:, None, :] * ohi[None, :, :])
                                 .reshape(N_COMP * N_HI, blk))
                rhs_parts.append((lo[f][None, :] == iota_lo)
                                 .astype(odt))                 # [32, blk]
            lhs = jnp.concatenate(lhs_parts, axis=0)           # [96, blk]
            # rhs stays lane-major [128, blk]: contracting BOTH operands
            # on the row (lane) dim avoids the [blk, 32] one-hot
            # transpose relayout
            rhs = jnp.concatenate(rhs_parts, axis=0)           # [128, blk]
            part = jax.lax.dot_general(
                lhs, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=adt)                    # [96, 128]
            if init:
                out_ref[0, m, :, :] = part
            else:
                out_ref[0, m, :, :] += part

    @pl.when(r == 0)
    def _init():
        emit(True)

    @pl.when((r != 0) & active)
    def _acc():
        emit(False)


def _diag_hist_xla(out: jax.Array, fpad: int, hist_acc: str, inv_scale):
    """[groups, fb//4, 96, 128] accumulators -> [fpad, 256, 3] f32: the
    feature f == f' diagonal of the 4x4 block structure, dequantized."""
    part = out.reshape(-1, MM_FEATS, N_COMP, N_HI, MM_FEATS, N_LO)
    diag = jnp.einsum("gfchfl->gfchl", part)
    hist = diag.transpose(0, 1, 3, 4, 2).reshape(fpad, N_HI * N_LO,
                                                 N_COMP)
    return dequant_hist(hist, hist_acc, inv_scale)


def _hist_kernel(hist_acc, target_ref, bins_ref, gh_ref, leaf_ref,
                 out_ref):
    r = pl.program_id(1)
    _accumulate(target_ref[0], bins_ref, gh_ref, leaf_ref, out_ref, r,
                True, hist_acc)


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "hist_acc", "row_block",
                                    "interpret"))
def leaf_histogram_masked(bins_t: jax.Array, gh2: jax.Array,
                          leaf_eff: jax.Array, target_leaf,
                          inv_scale=None, *, max_bin: int,
                          hist_acc: str = "f32",
                          row_block: int = PALLAS_ROW_BLOCK,
                          interpret: bool = False) -> jax.Array:
    """Histogram over rows with leaf_eff == target_leaf.

    bins_t [F, N] uint8; gh2 [2, N] in the hist_acc streaming dtype
    (see make_gh2_acc) — built ONCE per tree; leaf_eff [N] i32 with
    bagging folded in (see fold_leaf_mask).
    Returns hist [F, max_bin, 3] f32 with components (grad, hess, count).
    """
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    fb, fpad, groups = _feat_grid(f)
    nblocks = n // row_block
    target = jnp.asarray(target_leaf, dtype=jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, hist_acc),
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
            (groups, fb // MM_FEATS, M_ROWS, N_COLS),
            _acc_dtype(hist_acc)),
        interpret=interpret,
        name="leaf_histogram_masked",
    )(target, bins_t, gh2, leaf_eff)
    # rows are (f, c, hi), cols are (f', lo); feature f's histogram is the
    # f == f' diagonal of the 4x4 block structure
    hist = _diag_hist_xla(out, fpad, hist_acc, inv_scale)
    return hist[:f, :max_bin, :]


def _hist_body(hist_acc, info_ref, bins_ref, gh_ref, leaf_ref, out_ref):
    """Shared body of the ranged/blocklist kernels: info = [target, _,
    n_active] (SMEM).

    The grid's row dimension is the static worst case; steps past
    n_active revisit the last active block (index maps clamp), so the
    pipeline skips their DMA, and pl.when skips their matmuls — the cost
    of an inactive step is grid bookkeeping only.  This is what makes
    sweep time proportional to the leaf's block count instead of N.
    """
    r = pl.program_id(1)
    _accumulate(info_ref[0], bins_ref, gh_ref, leaf_ref, out_ref, r,
                r < info_ref[2], hist_acc)


def _hist_kernel_ranged(hist_acc, info_ref, bins_ref, gh_ref, leaf_ref,
                        out_ref):
    _hist_body(hist_acc, info_ref, bins_ref, gh_ref, leaf_ref, out_ref)


def _hist_kernel_blocklist(hist_acc, info_ref, blist_ref, bins_ref,
                           gh_ref, leaf_ref, out_ref):
    # blist_ref is consumed by the index maps; the body only needs info
    _hist_body(hist_acc, info_ref, bins_ref, gh_ref, leaf_ref, out_ref)


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "hist_acc", "row_block",
                                    "interpret"))
def leaf_histogram_ranged(bins_t: jax.Array, gh2: jax.Array,
                          leaf_eff: jax.Array, target_leaf, start_block,
                          n_active, inv_scale=None, *, max_bin: int,
                          hist_acc: str = "f32",
                          row_block: int = PALLAS_ROW_BLOCK,
                          interpret: bool = False) -> jax.Array:
    """leaf_histogram_masked restricted to row blocks
    [start_block, start_block + n_active) — correct whenever every row
    with leaf_eff == target_leaf lies inside that block range (the
    ordered-partition invariant; rows of OTHER leaves inside the range
    are masked out as usual).  start_block/n_active are traced scalars:
    one compiled kernel serves every leaf range."""
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    fb, fpad, groups = _feat_grid(f)
    nblocks = n // row_block
    # n_active >= 1 keeps the clamp and the r==0 init well-defined; an
    # EMPTY target leaf stays correct because the in-kernel mask
    # (leaf_eff == target) selects nothing in whatever block is swept
    info = jnp.stack([jnp.asarray(target_leaf, jnp.int32),
                      jnp.clip(jnp.asarray(start_block, jnp.int32), 0,
                               nblocks - 1),
                      jnp.maximum(jnp.asarray(n_active, jnp.int32), 1)])

    def _rb(r, info_ref):
        # clamp to the last active block: inactive steps re-request it,
        # which the pipeline recognizes as "same block, no copy"
        return info_ref[1] + jnp.minimum(r, info_ref[2] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(groups, nblocks),
        in_specs=[
            pl.BlockSpec((fb, row_block), lambda i, r, s: (i, _rb(r, s))),
            pl.BlockSpec((2, row_block), lambda i, r, s: (0, _rb(r, s))),
            pl.BlockSpec((row_block,), lambda i, r, s: (_rb(r, s),)),
        ],
        out_specs=pl.BlockSpec((1, fb // MM_FEATS, M_ROWS, N_COLS),
                               lambda i, r, s: (i, 0, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_hist_kernel_ranged, hist_acc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (groups, fb // MM_FEATS, M_ROWS, N_COLS),
            _acc_dtype(hist_acc)),
        interpret=interpret,
        name="leaf_histogram_ranged",
    )(info, bins_t, gh2, leaf_eff)
    hist = _diag_hist_xla(out, fpad, hist_acc, inv_scale)
    return hist[:f, :max_bin, :]


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "hist_acc", "grid_blocks",
                                    "row_block", "interpret"))
def leaf_histogram_blocklist(bins_t: jax.Array, gh2: jax.Array,
                             leaf_eff: jax.Array, target_leaf,
                             block_list: jax.Array, n_active,
                             inv_scale=None, *,
                             max_bin: int, hist_acc: str = "f32",
                             grid_blocks: int = 0,
                             row_block: int = PALLAS_ROW_BLOCK,
                             interpret: bool = False) -> jax.Array:
    """leaf_histogram_masked restricted to the row blocks named by
    block_list[:n_active] (any order; ascending preserves the full
    sweep's accumulation association, making the result BIT-identical to
    it — skipped blocks contribute exact +0.0f).  Correct whenever every
    row with leaf_eff == target_leaf lies in a listed block; rows of
    other leaves in listed blocks are masked as usual.

    grid_blocks statically bounds the grid (and therefore the per-call
    floor cost); callers dispatch over a ladder of compiled variants and
    pick the smallest with grid_blocks >= n_active.  Steps past n_active
    revisit the last listed block (no DMA) and skip their matmuls.
    """
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    fb, fpad, groups = _feat_grid(f)
    nblocks = n // row_block
    if grid_blocks <= 0 or grid_blocks > nblocks:
        grid_blocks = nblocks
    info = jnp.stack([jnp.asarray(target_leaf, jnp.int32),
                      jnp.int32(0),
                      jnp.clip(jnp.asarray(n_active, jnp.int32), 1,
                               grid_blocks)])
    blist = jnp.clip(block_list.astype(jnp.int32), 0, nblocks - 1)

    def _rb(r, info_ref, blist_ref):
        return blist_ref[jnp.minimum(r, info_ref[2] - 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(groups, grid_blocks),
        in_specs=[
            pl.BlockSpec((fb, row_block),
                         lambda i, r, s, bl: (i, _rb(r, s, bl))),
            pl.BlockSpec((2, row_block),
                         lambda i, r, s, bl: (0, _rb(r, s, bl))),
            pl.BlockSpec((row_block,),
                         lambda i, r, s, bl: (_rb(r, s, bl),)),
        ],
        out_specs=pl.BlockSpec((1, fb // MM_FEATS, M_ROWS, N_COLS),
                               lambda i, r, s, bl: (i, 0, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_hist_kernel_blocklist, hist_acc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (groups, fb // MM_FEATS, M_ROWS, N_COLS),
            _acc_dtype(hist_acc)),
        interpret=interpret,
        name="leaf_histogram_blocklist",
    )(info, blist, bins_t, gh2, leaf_eff)
    hist = _diag_hist_xla(out, fpad, hist_acc, inv_scale)
    return hist[:f, :max_bin, :]


def leaf_histogram_pallas(bins_t: jax.Array, gh2: jax.Array,
                          mask: jax.Array, *, max_bin: int,
                          row_block: int = PALLAS_ROW_BLOCK,
                          interpret: bool = False) -> jax.Array:
    """Histogram of mask-selected rows: thin wrapper over the fused-mask
    kernel with the mask folded into a single-leaf leaf_eff."""
    leaf_eff = fold_leaf_mask(jnp.zeros(bins_t.shape[1], jnp.int32), mask)
    return leaf_histogram_masked(bins_t, gh2, leaf_eff, jnp.int32(0),
                                 max_bin=max_bin, row_block=row_block,
                                 interpret=interpret)


# ---------------------------------------------------------------------------
# Fused histogram + best-split gain scan (round 16)
# ---------------------------------------------------------------------------

def _fused_scan_tail(info_ref, stats_ref, parent_ref, fmask_ref, out_ref,
                     pfs_ref, pfl_ref, r_last, max_bin, params, hist_acc):
    """The in-kernel gain-scan epilogue every fused variant shares: on
    the LAST grid step — the feature block's accumulators complete and
    still VMEM-resident — extract the block-diagonal into per-feature
    [B, 3] histograms, run `per_feature_split_rows` (the oracle scan's
    exact jnp ops) for the swept child, subtract from the streamed-in
    parent block and scan the sibling, and emit one [fb, 8] best row
    per child.  The [F, B, 3] tensor is never read back from HBM for
    scanning."""
    r = pl.program_id(1)

    @pl.when(r == r_last)
    def _scan():
        fb = fmask_ref.shape[0]
        acc = out_ref[0]                 # [fb//4, 96, 128] acc dtype
        rows = []
        for m in range(fb // MM_FEATS):
            for f in range(MM_FEATS):
                sub = acc[m, f * (N_COMP * N_HI):(f + 1)
                          * (N_COMP * N_HI),
                          f * N_LO:(f + 1) * N_LO]       # [24, 32]
                rows.append(sub.reshape(N_COMP, N_HI * N_LO))
        h3 = jnp.stack(rows).astype(jnp.float32)         # [fb, 3, 256]
        if hist_acc == "i32":
            inv = stats_ref[SF_INV]
            h3 = h3 * jnp.stack([inv, inv,
                                 jnp.float32(1.0)])[None, :, None]
        # slice to max_bin BEFORE the scan: literally the oracle's
        # [F, max_bin, 3] input, so the suffix sums see identical arrays
        hist = h3[:, :, :max_bin].transpose(0, 2, 1)     # [fb, B, 3]
        fmask = fmask_ref[...] > 0
        pfs_ref[...] = per_feature_split_rows(
            hist, info_ref[IF_CNT_S], stats_ref[SF_SG_S],
            stats_ref[SF_SH_S], fmask, params)
        large = parent_ref[...].astype(jnp.float32) - hist
        pfl_ref[...] = per_feature_split_rows(
            large, info_ref[IF_CNT_L], stats_ref[SF_SG_L],
            stats_ref[SF_SH_L], fmask, params)


def _hist_fused_kernel(hist_acc, max_bin, params, nblocks, info_ref,
                       stats_ref, bins_ref, gh_ref, leaf_ref, parent_ref,
                       fmask_ref, out_ref, pfs_ref, pfl_ref):
    r = pl.program_id(1)
    _accumulate(info_ref[0], bins_ref, gh_ref, leaf_ref, out_ref, r,
                True, hist_acc)
    _fused_scan_tail(info_ref, stats_ref, parent_ref, fmask_ref, out_ref,
                     pfs_ref, pfl_ref, nblocks - 1, max_bin, params,
                     hist_acc)


def _hist_fused_kernel_ranged(hist_acc, max_bin, params, nblocks,
                              info_ref, stats_ref, bins_ref, gh_ref,
                              leaf_ref, parent_ref, fmask_ref, out_ref,
                              pfs_ref, pfl_ref):
    r = pl.program_id(1)
    _accumulate(info_ref[0], bins_ref, gh_ref, leaf_ref, out_ref, r,
                r < info_ref[IF_ACTIVE], hist_acc)
    _fused_scan_tail(info_ref, stats_ref, parent_ref, fmask_ref, out_ref,
                     pfs_ref, pfl_ref, nblocks - 1, max_bin, params,
                     hist_acc)


def _ranged_fused_specs(fb, row_block, max_bin):
    """in/out specs of the ranged fused kernel (info + stats scalar-
    prefetched; index maps clamp to the last active block)."""
    def _rb(r, info_ref):
        return info_ref[1] + jnp.minimum(r, info_ref[IF_ACTIVE] - 1)

    in_specs = [
        pl.BlockSpec((fb, row_block),
                     lambda i, r, s, st: (i, _rb(r, s))),
        pl.BlockSpec((2, row_block),
                     lambda i, r, s, st: (0, _rb(r, s))),
        pl.BlockSpec((row_block,), lambda i, r, s, st: (_rb(r, s),)),
        pl.BlockSpec((fb, max_bin, 3), lambda i, r, s, st: (i, 0, 0)),
        pl.BlockSpec((fb,), lambda i, r, s, st: (i,)),
    ]
    out_specs = (
        pl.BlockSpec((1, fb // MM_FEATS, M_ROWS, N_COLS),
                     lambda i, r, s, st: (i, 0, 0, 0)),
        pl.BlockSpec((fb, PF_COLS), lambda i, r, s, st: (i, 0)),
        pl.BlockSpec((fb, PF_COLS), lambda i, r, s, st: (i, 0)),
    )
    return in_specs, out_specs


def _hist_fused_kernel_blocklist(hist_acc, max_bin, params, grid_blocks,
                                 info_ref, stats_ref, blist_ref, bins_ref,
                                 gh_ref, leaf_ref, parent_ref, fmask_ref,
                                 out_ref, pfs_ref, pfl_ref):
    r = pl.program_id(1)
    _accumulate(info_ref[0], bins_ref, gh_ref, leaf_ref, out_ref, r,
                r < info_ref[IF_ACTIVE], hist_acc)
    _fused_scan_tail(info_ref, stats_ref, parent_ref, fmask_ref, out_ref,
                     pfs_ref, pfl_ref, grid_blocks - 1, max_bin, params,
                     hist_acc)


def _fused_prep(f, parent_hist, feature_mask,
                small_stats, large_stats, inv_scale, max_bin):
    """Shared padding + SMEM packing of the fused wrappers.  Returns
    (parent, fmask_f, info_tail, stats, fb, fpad, groups)."""
    fb, fpad, groups = _feat_grid(f)
    if fpad != f:
        # kilobytes; the bin matrix itself stays as it is (_feat_grid),
        # and the False mask is what keeps a feature past F, whatever
        # its rows held, from winning the in-kernel scan
        parent_hist = jnp.pad(parent_hist,
                              ((0, fpad - f), (0, 0), (0, 0)))
        feature_mask = jnp.pad(feature_mask, (0, fpad - f))
    cnt_s, sg_s, sh_s = small_stats
    cnt_l, sg_l, sh_l = large_stats
    info_tail = [jnp.asarray(cnt_s, jnp.int32),
                 jnp.asarray(cnt_l, jnp.int32),
                 jnp.int32(0), jnp.int32(0), jnp.int32(0)]
    inv = (jnp.float32(1.0) if inv_scale is None
           else jnp.asarray(inv_scale, jnp.float32))
    f32 = jnp.float32
    stats = jnp.stack([jnp.asarray(sg_s, f32), jnp.asarray(sh_s, f32),
                       jnp.asarray(sg_l, f32), jnp.asarray(sh_l, f32),
                       inv, f32(0), f32(0), f32(0)])
    fmask_f = feature_mask.astype(jnp.float32)
    return (parent_hist.astype(jnp.float32), fmask_f, info_tail, stats,
            fb, fpad, groups)


def _fused_outs(groups, fb, fpad, hist_acc):
    out_shape = (
        jax.ShapeDtypeStruct((groups, fb // MM_FEATS, M_ROWS, N_COLS),
                             _acc_dtype(hist_acc)),
        jax.ShapeDtypeStruct((fpad, PF_COLS), jnp.float32),
        jax.ShapeDtypeStruct((fpad, PF_COLS), jnp.float32),
    )
    return out_shape


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "params", "hist_acc",
                                    "row_block", "interpret"))
def leaf_histogram_masked_fused(bins_t: jax.Array, gh2: jax.Array,
                                leaf_eff: jax.Array, target_leaf,
                                parent_hist: jax.Array,
                                feature_mask: jax.Array, small_stats,
                                large_stats, inv_scale=None, *,
                                max_bin: int, params: SplitParams,
                                hist_acc: str = "f32",
                                row_block: int = PALLAS_ROW_BLOCK,
                                interpret: bool = False):
    """Fused sweep + gain scan for one split's two children.

    Sweeps the rows with leaf_eff == target_leaf (the SMALL child),
    exactly like leaf_histogram_masked, and on the last grid step also
    scans small AND (parent - small) in-register.  small_stats /
    large_stats are (count i32, sum_g, sum_h) leaf totals; parent_hist
    is the parent's [F, max_bin, 3] f32 histogram (pool state).

    Returns (small_hist [F, max_bin, 3] f32, pf_small [F, 8],
    pf_large [F, 8]) — pf rows finish through
    ops/split.find_best_split_fused.
    """
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    (parent, fmask_f, info_tail, stats, fb, fpad,
     groups) = _fused_prep(f, parent_hist, feature_mask,
                           small_stats, large_stats, inv_scale,
                           max_bin)
    nblocks = n // row_block
    info = jnp.stack([jnp.asarray(target_leaf, jnp.int32),
                      jnp.int32(0), jnp.int32(nblocks)] + info_tail)

    out, pfs, pfl = pl.pallas_call(
        functools.partial(_hist_fused_kernel, hist_acc, max_bin, params,
                          nblocks),
        grid=(groups, nblocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((fb, row_block), lambda i, r: (i, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, row_block), lambda i, r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((row_block,), lambda i, r: (r,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((fb, max_bin, 3), lambda i, r: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((fb,), lambda i, r: (i,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, fb // MM_FEATS, M_ROWS, N_COLS),
                         lambda i, r: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((fb, PF_COLS), lambda i, r: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((fb, PF_COLS), lambda i, r: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=_fused_outs(groups, fb, fpad, hist_acc),
        interpret=interpret,
        name="leaf_histogram_masked_fused",
    )(info, stats, bins_t, gh2, leaf_eff, parent, fmask_f)
    hist = _diag_hist_xla(out, fpad, hist_acc, inv_scale)
    return hist[:f, :max_bin, :], pfs[:f], pfl[:f]


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "params", "hist_acc",
                                    "grid_blocks", "row_block",
                                    "interpret"))
def leaf_histogram_blocklist_fused(bins_t: jax.Array, gh2: jax.Array,
                                   leaf_eff: jax.Array, target_leaf,
                                   block_list: jax.Array, n_active,
                                   parent_hist: jax.Array,
                                   feature_mask: jax.Array, small_stats,
                                   large_stats, inv_scale=None, *,
                                   max_bin: int, params: SplitParams,
                                   hist_acc: str = "f32",
                                   grid_blocks: int = 0,
                                   row_block: int = PALLAS_ROW_BLOCK,
                                   interpret: bool = False):
    """leaf_histogram_blocklist + the fused gain-scan epilogue: the
    ordered-partition fast path keeps its leaf-proportional sweeps AND
    drops the two XLA scan passes.  Same contract as
    leaf_histogram_masked_fused; same block-list correctness rule as
    leaf_histogram_blocklist."""
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    (parent, fmask_f, info_tail, stats, fb, fpad,
     groups) = _fused_prep(f, parent_hist, feature_mask,
                           small_stats, large_stats, inv_scale,
                           max_bin)
    nblocks = n // row_block
    if grid_blocks <= 0 or grid_blocks > nblocks:
        grid_blocks = nblocks
    info = jnp.stack([jnp.asarray(target_leaf, jnp.int32),
                      jnp.int32(0),
                      jnp.clip(jnp.asarray(n_active, jnp.int32), 1,
                               grid_blocks)] + info_tail)
    blist = jnp.clip(block_list.astype(jnp.int32), 0, nblocks - 1)

    def _rb(r, info_ref, blist_ref):
        return blist_ref[jnp.minimum(r, info_ref[IF_ACTIVE] - 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # info, stats, blist
        grid=(groups, grid_blocks),
        in_specs=[
            pl.BlockSpec((fb, row_block),
                         lambda i, r, s, st, bl: (i, _rb(r, s, bl))),
            pl.BlockSpec((2, row_block),
                         lambda i, r, s, st, bl: (0, _rb(r, s, bl))),
            pl.BlockSpec((row_block,),
                         lambda i, r, s, st, bl: (_rb(r, s, bl),)),
            pl.BlockSpec((fb, max_bin, 3),
                         lambda i, r, s, st, bl: (i, 0, 0)),
            pl.BlockSpec((fb,), lambda i, r, s, st, bl: (i,)),
        ],
        out_specs=(
            pl.BlockSpec((1, fb // MM_FEATS, M_ROWS, N_COLS),
                         lambda i, r, s, st, bl: (i, 0, 0, 0)),
            pl.BlockSpec((fb, PF_COLS), lambda i, r, s, st, bl: (i, 0)),
            pl.BlockSpec((fb, PF_COLS), lambda i, r, s, st, bl: (i, 0)),
        ),
    )
    out, pfs, pfl = pl.pallas_call(
        functools.partial(_hist_fused_kernel_blocklist, hist_acc,
                          max_bin, params, grid_blocks),
        grid_spec=grid_spec,
        out_shape=_fused_outs(groups, fb, fpad, hist_acc),
        interpret=interpret,
        name="leaf_histogram_blocklist_fused",
    )(info, stats, blist, bins_t, gh2, leaf_eff, parent, fmask_f)
    hist = _diag_hist_xla(out, fpad, hist_acc, inv_scale)
    return hist[:f, :max_bin, :], pfs[:f], pfl[:f]


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "params", "hist_acc",
                                    "row_block", "interpret"))
def leaf_histogram_ranged_fused(bins_t: jax.Array, gh2: jax.Array,
                                leaf_eff: jax.Array, target_leaf,
                                start_block, n_active,
                                parent_hist: jax.Array,
                                feature_mask: jax.Array, small_stats,
                                large_stats, inv_scale=None, *,
                                max_bin: int, params: SplitParams,
                                hist_acc: str = "f32",
                                row_block: int = PALLAS_ROW_BLOCK,
                                interpret: bool = False):
    """leaf_histogram_ranged + the fused gain-scan epilogue.  Same
    contract as leaf_histogram_masked_fused; same contiguous-range
    correctness rule as leaf_histogram_ranged.

    Like its non-fused twin, this variant is not on the grow_tree
    routing (the ordered-partition mode builds block lists and fuses
    through leaf_histogram_blocklist_fused) — it is the maintained
    contiguous-range API for callers that track leaf extents instead
    of block lists, parity-pinned at the kernel level by
    tests/test_hist_fused.py."""
    f, n = bins_t.shape
    assert n % row_block == 0, (n, row_block)
    assert max_bin <= N_HI * N_LO, max_bin
    (parent, fmask_f, info_tail, stats, fb, fpad,
     groups) = _fused_prep(f, parent_hist, feature_mask,
                           small_stats, large_stats, inv_scale,
                           max_bin)
    nblocks = n // row_block
    info = jnp.stack([jnp.asarray(target_leaf, jnp.int32),
                      jnp.clip(jnp.asarray(start_block, jnp.int32), 0,
                               nblocks - 1),
                      jnp.maximum(jnp.asarray(n_active, jnp.int32), 1)]
                     + info_tail)
    in_specs, out_specs = _ranged_fused_specs(fb, row_block, max_bin)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # info, stats
        grid=(groups, nblocks),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    out, pfs, pfl = pl.pallas_call(
        functools.partial(_hist_fused_kernel_ranged, hist_acc, max_bin,
                          params, nblocks),
        grid_spec=grid_spec,
        out_shape=_fused_outs(groups, fb, fpad, hist_acc),
        interpret=interpret,
        name="leaf_histogram_ranged_fused",
    )(info, stats, bins_t, gh2, leaf_eff, parent, fmask_f)
    hist = _diag_hist_xla(out, fpad, hist_acc, inv_scale)
    return hist[:f, :max_bin, :], pfs[:f], pfl[:f]
