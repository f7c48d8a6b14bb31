"""GBDT boosting driver.

Python/JAX host loop replacing the reference's GBDT class
(src/boosting/gbdt.cpp): per-iteration flow is gradients -> per-class tree
growth (one fused device call per tree, ops/grow.py) -> score updates ->
metrics/early-stopping.  Model text format is byte-compatible with
GBDT::SaveModelToFile / LoadModelFromString (gbdt.cpp:351-456).

Bagging (row- and query-granular reservoir sampling, gbdt.cpp:109-160) and
feature_fraction (serial_tree_learner.cpp:140-147) reproduce the reference's
mt19937 draw streams bit-exactly (utils/mt19937.py), enabling tree-identity
parity tests with bagging enabled.
"""

from __future__ import annotations

__jax_free__ = False  # the boosting driver traces jits

import functools
from collections import OrderedDict
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..analysis.contracts import contract
from ..config import Config
from ..io.dataset import Dataset
from ..metrics import Metric
from ..objectives import Objective
from ..ops.grow import grow_tree, grow_tree_bagged
from ..ops.predict import predict_leaf_binned, replay_leaf_binned_inline
from ..ops.split import SplitParams
from ..resilience.atomic import read_npz, text_writer, write_npz
from ..resilience.snapshot import fingerprint_diff, resume_fingerprint
from ..resilience.faults import faultpoint
from ..utils import compile_cache, log, spans
from ..utils.mt19937 import Mt19937Random
from .tree import Tree

NO_LIMIT = -1


class _PendingTree:
    """A trained tree still packed in device buffers; GBDT._flush_pending
    stacks every pending tree's buffers and pulls them host-side in one
    transfer, then unpacks them into host Trees — the per-iteration
    dispatch pipeline never blocks on a device->host roundtrip.

    Invariant: every pending ints/floats pair in one booster has the
    SAME shape — _pack_tree pads to the config-fixed leaf count, and
    the flush's jnp.stack relies on it (asserted there)."""

    __slots__ = ("ints", "floats", "lr", "gated")

    def __init__(self, ints, floats, lr, gated=False):
        # gated: produced by the fused step, whose device stopped-flag
        # already suppressed this tree's score updates if it came after
        # a stump — _flush_pending must NOT subtract it again
        self.ints = ints
        self.floats = floats
        self.lr = lr
        self.gated = gated


@jax.jit
def _pack_tree(dev_tree):
    """TreeArrays -> (int32 buffer, float buffer): two flat arrays so a
    whole tree ships device->host in two async copies instead of eleven.
    The trailing dummy slots (grow.py TreeArrays) are trimmed here, so the
    wire layout stays [1 + 4*(L-1) + 3*L + 4 | (L-1) + L + (L-1)]: the
    int row ends with the tree's four sweep counters (blocks_swept,
    grid_rows, partition_blocks, rows_swept), behind everything
    _unpack_tree and _dart_layout slice."""
    ints = jnp.concatenate([
        dev_tree.num_leaves.reshape(1), dev_tree.split_feature[:-1],
        dev_tree.threshold_bin[:-1], dev_tree.left_child[:-1],
        dev_tree.right_child[:-1], dev_tree.leaf_parent[:-1],
        dev_tree.leaf_depth[:-1], dev_tree.leaf_count[:-1],
        dev_tree.blocks_swept.reshape(1), dev_tree.grid_rows.reshape(1),
        dev_tree.partition_blocks.reshape(1), dev_tree.rows_swept.reshape(1),
    ]).astype(jnp.int32)
    floats = jnp.concatenate([dev_tree.split_gain[:-1],
                              dev_tree.leaf_value[:-1],
                              dev_tree.internal_value[:-1]])
    return ints, floats


# Shared fused-iteration executables, keyed by everything static that
# shapes the computation (objective fused_key, lr, dtype, grow params,
# valid-set count).  Bins, labels and scores are jit ARGUMENTS, so the
# executable embeds no dataset constants: it stays small (MBs, not 100s
# of MBs), the persistent compilation cache entry is shape-keyed and
# reusable across processes, and a warm-up booster and the real booster
# share one compilation.  LRU-bounded so a long hyper-parameter sweep
# doesn't accumulate executables forever (evicted entries recompile via
# the persistent disk cache, which is cheap).
_FUSED_STEPS = OrderedDict()
_FUSED_STEPS_MAX = 8


def _get_fused_step(key, make):
    """LRU lookup of a fused executable; `make()` builds it on miss."""
    fn = _FUSED_STEPS.get(key)
    if fn is None:
        fn = make()
        _FUSED_STEPS[key] = fn
        if len(_FUSED_STEPS) > _FUSED_STEPS_MAX:
            _FUSED_STEPS.popitem(last=False)
    else:
        _FUSED_STEPS.move_to_end(key)
    return fn


def _unpack_bag(bag_mask, n_pad):
    """Bag masks upload as packed bits ([n_pad/8] u8, np.packbits big-
    endian bit order) — 8x less host->device traffic per re-bagging.
    Bool masks pass through."""
    if bag_mask.dtype == jnp.uint8:
        bits = (bag_mask[:, None]
                >> (jnp.uint8(7) - jnp.arange(8, dtype=jnp.uint8))) \
            & jnp.uint8(1)
        return bits.reshape(-1)[:n_pad].astype(bool)
    return bag_mask


_unpack_bag_jit = jax.jit(_unpack_bag, static_argnums=1)


@jax.jit
def _permute_packed_bag(packed: jax.Array, row_order: jax.Array):
    """File-order packed bag bits -> ordered-space bool mask (a gather of
    every row: part of what a redraw costs the device, so it carries the
    arrangement's scope)."""
    with jax.named_scope(spans.BAG_ARRANGE):
        return jnp.take(_unpack_bag(packed, row_order.shape[0]), row_order)


# -- iteration batching (config.iter_batch) ---------------------------------
#
# One GENERIC wrapper turns any fused step body into a K-iteration body:
# an outer lax.scan whose carry is the cross-iteration device state
# (scores, valid scores, the stopped flag, and — on the reorder variants —
# bins/bag/gstate/row order), whose xs are the per-iteration HOST inputs
# (feature masks; DART adds drop lists, shrinkage factors and bank rows),
# and whose ys are the K packed trees, stacked [K, T_ints]/[K, T_floats]
# and pulled host-side in one transfer by the usual deferred flush.  The
# wrapped body keeps the original positional signature, so the jit/
# shard_map plumbing (donation positions, partition specs) is untouched —
# replicated specs (P()) hold for the [K, ...] xs/ys regardless of rank.
#
# A spec is (carry (in_pos, out_pos) pairs, xs in positions, ys out
# positions, output arity); everything else is segment-constant and stays
# closed over via the outer args.

_SCAN_PLAIN = (((0, 0), (1, 1), (7, 4)), (3,), (2, 3), 5)
_SCAN_REORDER = (((0, 0), (1, 1), (2, 5), (4, 4), (6, 6), (7, 7), (8, 8),
                  (9, 9)), (3,), (2, 3), 10)
_SCAN_MULTI = (((0, 0), (1, 1), (7, 4)), (3,), (2, 3), 5)
_SCAN_MULTI_REORDER = (((0, 0), (1, 1), (2, 6), (4, 5), (6, 7), (7, 4),
                        (8, 8)), (3,), (2, 3), 9)
_SCAN_DART = (((0, 0), (1, 1), (2, 2), (3, 3), (4, 4), (5, 5), (15, 8)),
              (6, 7, 8, 9, 11, 16), (6, 7), 9)
_SCAN_DART_REORDER = (_SCAN_DART[0] + ((12, 9), (10, 10), (14, 11), (17, 12)),
                      _SCAN_DART[1], _SCAN_DART[2], 13)


@contract.parity_oracle("K=1 returns the body UNCHANGED — the "
                        "per-iteration oracle executes the very same "
                        "closure, so K>1 is bit-parity by construction")
def _batch_iters(body, spec, k):
    """Wrap a fused step body in an outer lax.scan over `k` boosting
    iterations.  k == 1 returns the body unchanged — the per-iteration
    oracle executes the very same closure, so K>1 is bit-parity with it
    by construction (same ops, same order, iterated by the scan)."""
    if k <= 1:
        return body
    carry_map, xs_pos, ys_pos, n_out = spec

    def batched(*args):
        carry0 = tuple(args[i] for i, _ in carry_map)
        xs = tuple(args[i] for i in xs_pos)

        def scan_body(carry, x):
            call = list(args)
            for (i, _), v in zip(carry_map, carry):
                call[i] = v
            for i, v in zip(xs_pos, x):
                call[i] = v
            outs = body(*call)
            return (tuple(outs[o] for _, o in carry_map),
                    tuple(outs[o] for o in ys_pos))

        carry, ys = jax.lax.scan(scan_body, carry0, xs)
        out = [None] * n_out
        for (_, o), v in zip(carry_map, carry):
            out[o] = v
        for o, v in zip(ys_pos, ys):
            out[o] = v
        return tuple(out)
    return batched


# Device-dispatch accounting: every training-path executable is called
# inside `with _enqueue(kind, k):`, which counts the dispatch and opens
# the profiler's lgbm.enqueue host span (utils/spans.py) in one place, so
# the count and the spans cannot drift.  dispatch_count() is read by the
# benchmark's trees_per_dispatch.  A host counter, not a guard —
# analysis/guards.py counts the transfers.
_DISPATCHES = 0
_FIRST_CALLS: set = set()    # the (kind, k, shards) that had a first call


class _enqueue:
    """The span around one call of a jitted training executable:
    `kind` names it (spans.ENQUEUE_KINDS), `k` is the boosting
    iterations it covers, `shards` the devices the one program runs on;
    a dispatch that re-sorts the rows adds `carried`, `taken` and
    `word_rows` (_resort_counts).  The call returns when the work is
    enqueued, not when the device is done (unless it compiles first).

    While it is open it is the compile ledger's context
    (utils/compile_cache.py): an executable traced, lowered, compiled or
    loaded inside it lands in `compiled`, and the call was a FIRST call.
    It then leaves a start-up record lgbm.first_call (the call's
    seconds, the ledger's sums for it, `hit`: the persistent cache had
    every executable, `again`: this (kind, k, shards) had a first call
    before, so the same plan compiled anew) and tells the span
    (`first=1`, `hit`).  Off the first call that is one test a
    dispatch."""

    __slots__ = ("key", "compiled", "_span", "_open", "_t0")

    def __init__(self, kind: str, k: int, shards: int = 1,
                 **stats: int) -> None:
        global _DISPATCHES
        if not _DISPATCHES:
            spans.stamp(spans.FIRST_DISPATCH)
        _DISPATCHES += 1
        self.key = (kind, k, shards)
        self.compiled: Optional[list] = None
        self._span = TraceAnnotation(spans.ENQUEUE, kind=kind, k=k,
                                     shards=shards, **stats)

    def compiled_inside(self, record: dict) -> list:
        """The ledger's hand-over of an executable of this call; -> the
        call, as the record names it."""
        if self.compiled is None:
            self.compiled = []
        self.compiled.append(record)
        return list(self.key)

    def __enter__(self) -> None:
        self._span.__enter__()
        self._open = spans.open_contexts()
        self._open.append(self)
        self._t0 = spans.clock()

    def __exit__(self, *exc) -> None:
        self._open.pop()
        if self.compiled is not None:
            self._first_call()
        self._span.__exit__(*exc)

    def _first_call(self) -> None:
        seconds = spans.clock() - self._t0
        sums = {f: sum(r[f] for r in self.compiled)
                for f in ("trace_s", "lower_s", "backend_s", "retrieval_s")}
        hit = int(all(r["hit"] for r in self.compiled
                      if r["hit"] is not None))
        again = int(self.key in _FIRST_CALLS)
        _FIRST_CALLS.add(self.key)
        kind, k, shards = self.key
        self._span.set_metadata(first=1, hit=hit)
        spans.record_startup(spans.FIRST_CALL, self._t0, seconds, kind=kind,
                             k=k, shards=shards,
                             executables=len(self.compiled), hit=hit,
                             again=again, **sums)


def dispatch_count() -> int:
    """Total training-path device dispatches this process has issued."""
    return _DISPATCHES


@contract.traced_pure
@contract.parity_oracle("the plain fused body: bag_compact=off / "
                        "masked-bagging oracle (PARITY.md §2.3)")
def _fused_step_body(grad_fn, grow_kw, lr, dtype, compact_rows=0):
    def step(scores, valid_scores, bag_mask, fmask, bins, valid_bins,
             gstate, stopped):
        bag = _unpack_bag(bag_mask, bins.shape[1])
        with jax.named_scope(spans.OBJECTIVE):
            grad, hess = grad_fn(scores[0], gstate)
            grad, hess = grad.astype(dtype), hess.astype(dtype)
        with jax.named_scope(spans.GROW):
            dev_tree, leaf_id = grow_tree_bagged(
                bins, grad, hess, bag, fmask, bag_rows=compact_rows,
                **grow_kw)
        # deferred stump stop: once any tree fails to split, every later
        # step no-ops its score updates, so a late host flush truncates
        # at the exact reference stop point (gbdt.cpp:186) with scores
        # untouched past it — no per-iteration host sync needed even
        # with bagging/feature_fraction
        live = jnp.logical_not(stopped)
        stopped = stopped | (dev_tree.num_leaves <= 1)
        with jax.named_scope(spans.SCORE_UPDATE):
            leaf_vals = jnp.where(live, dev_tree.leaf_value * lr,
                                  0.0).astype(jnp.float32)
            scores = scores.at[0].add(leaf_vals[leaf_id])
        new_valid = []
        with jax.named_scope(spans.VALID_UPDATE):
            for vs, vbins in zip(valid_scores, valid_bins):
                vleaf = predict_leaf_binned(
                    dev_tree.split_feature, dev_tree.threshold_bin,
                    dev_tree.left_child, dev_tree.right_child, vbins)
                new_valid.append(vs.at[0].add(leaf_vals[vleaf]))
        with jax.named_scope(spans.PACK_TREE):
            ints, floats = _pack_tree(dev_tree)
        return scores, new_valid, ints, floats, stopped
    return step


@contract.traced_pure
@contract.fused_body(collectives=("all_gather", "axis_index", "psum",
                                  "psum_scatter"))
def _make_fused_step(grad_fn, grow_kw, lr, dtype, compact_rows=0,
                     k_iters=1):
    body = _batch_iters(_fused_step_body(grad_fn, grow_kw, lr, dtype,
                                         compact_rows),
                        _SCAN_PLAIN, k_iters)
    return jax.jit(body, donate_argnums=(0, 1))


# The one gather of a re-sort moves a matrix of uint32 "word rows", and
# builds and takes it apart a block of columns at a time, so that what a
# re-sort holds beside the matrix itself stays small (PERF.md section 6,
# PR 36).  Both numbers follow from the shapes alone, never from a key:
# a group is at most _STACK_ROWS word rows (the ranking cell's 59 are one
# group), a block _BLOCK_COLS columns.  DART's [T, N] leaf bank is no such
# array: a stack of 64 word rows is 17.5 GB at 68.3M rows, more than the
# chip, so the bank rides as _FilledRows, a tile of 8 word rows at a time
# and only as far as it is filled.
_STACK_ROWS = 64
_BLOCK_COLS = 1 << 20
_TILE_WORDS = 8     # word rows of one (8, 128) uint32 tile: 32 uint8 rows


class _FilledRows(NamedTuple):
    """DART's leaf bank among a re-sort's buffers (the LAST of them):
    `rows` [R, N] of uint8 (int32 past 256 leaves) whose rows [0, fill)
    hold something, `fill` a RUN-TIME count (a traced scalar in a step,
    an int where the host counts: _resort_counts).  It moves in groups
    of _bank_group(rows) rows, each one stack of at most _TILE_WORDS word
    rows through the same `rel`, ceil(fill / group) of them: static
    shapes, a run-time trip count, in place."""
    rows: jax.Array
    fill: Any


def _bank_group(rows) -> Tuple[int, int]:
    """(rows, word rows) of one group of a _FilledRows in a re-sort: one
    (8, 128) tile of words, 32 uint8 rows (PERF.md section 6, PR 36: a
    gather costs by the tiles a column lies in, so 8 word rows move for
    the price of one), or all its rows where it has fewer.  The bank is
    allocated in whole groups (DART._plan_bank)."""
    per_word = 4 // rows.dtype.itemsize
    group = min(rows.shape[0], _TILE_WORDS * per_word)
    assert rows.shape[0] % group == 0 and group % per_word == 0, rows.shape
    return group, group // per_word


def _split_filled(bufs):
    """(the plain buffers, the _FilledRows among them)."""
    return ([b for b in bufs if not isinstance(b, _FilledRows)],
            [b for b in bufs if isinstance(b, _FilledRows)])


def _word_rows(a, n: int) -> int:
    """The rows an array fills in the stacked uint32 matrix that ONE
    gather moves in a re-sort, 0 if it follows by a gather of its own.
    Rows on the last axis; then a 32-bit array with one row a position
    ([N], or [1, N] like the single-class scores) is one row, bitcast;
    a narrower integer or bool with R rows a position (the bag's [N]
    bool, the [F, N] uint8/uint16 bins, [K, N] class-wise masks) is
    ceil(R x itemsize / 4) rows, 4 / itemsize of its rows to a word (a
    narrow float would change its value when
    widened).  64-bit state, narrow floats and 32-bit arrays with more
    than one row a position ([K, N] class-wise scores) are taken."""
    if a.shape[-1] != n:
        return 0
    rows, size = a.size // n, a.dtype.itemsize
    if size == 4:
        return int(rows == 1)
    if size < 4 and (a.dtype == jnp.bool_
                     or jnp.issubdtype(a.dtype, jnp.integer)):
        return -(-rows * size // 4)
    return 0


def _moves_as_word(a, n: int) -> bool:
    """Whether an array joins the stacked matrix (_word_rows): a gather
    on this chip costs by the INDEX and by the (8, 128) tiles a column
    of its operand lies in, hardly by the byte (17.7 ns an index for 5
    word rows, 27.4 for 15, where the bins alone cost 30.6: PERF.md
    section 6, PR 36), so every array that can be a few uint32 rows
    rides the one gather."""
    return _word_rows(a, n) > 0


def _resort_counts(bufs, gstate, row_state):
    """The `carried` / `taken` / `word_rows` stats of a re-sorting
    dispatch's lgbm.enqueue span: how many per-row arrays move together
    in the one gather of 32-bit words, how many follow by a gather of
    their own, and the rows of the stacked matrix (_resort_rows makes
    the same choice from the same shapes): 6, 0 and 15 for binary at
    F = 39, 5, 0 and 59 for lambdarank at F = 220.  An array that
    silently falls from the first count to the second costs a second
    and a half at 68M rows (PERF.md section 6, PRs 28 and 36).  DART's
    leaf bank (_FilledRows, `fill` an int here) is one more carried
    array, and adds the word rows of its filled groups."""
    bufs, banks = _split_filled(bufs)
    arrays = bufs + list(row_state(gstate)[0])
    n = arrays[0].shape[-1]
    rows = [_word_rows(a, n) for a in arrays]
    for bank in banks:
        group, words = _bank_group(bank.rows)
        rows.append(-(-int(bank.fill) // group) * words)
    carried = sum(r > 0 for r in rows) + sum(int(b.fill) == 0 for b in banks)
    return {"carried": carried, "taken": len(rows) - carried,
            "word_rows": sum(rows)}


def _word_parts(a, lo, hi):
    """(shift, first row, end row) of the rows of the narrow [R, n]
    array `a` that lie in its word rows [lo, hi): word g holds rows g,
    g + G, g + 2G, ... (G = all its word rows), one a byte (or half
    word): contiguous row slices, no [G, 4, n] view whose 4 the chip
    would pad to a tile."""
    bits = 8 * a.dtype.itemsize
    total = -(-a.shape[0] * bits // 32)
    for j in range(32 // bits):
        r0, r1 = j * total + lo, min(j * total + hi, a.shape[0])
        if r0 < r1:
            yield bits * j, r0, r1


def _pack_words(a, lo, hi, start, cols):
    """Word rows [lo, hi) of the [R, n] array `a`, columns [start,
    start + cols): uint32 [hi - lo, cols]."""
    if a.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(
            jax.lax.dynamic_slice(a, (0, start), (1, cols)), jnp.uint32)
    out = None
    for shift, r0, r1 in _word_parts(a, lo, hi):
        part = jax.lax.dynamic_slice(a, (r0, start), (r1 - r0, cols))
        if part.dtype != jnp.bool_:     # no sign extension
            part = jax.lax.bitcast_convert_type(
                part, jnp.uint8 if a.dtype.itemsize == 1 else jnp.uint16)
        part = part.astype(jnp.uint32) << shift
        if r1 - r0 < hi - lo:
            part = jnp.pad(part, ((0, hi - lo - (r1 - r0)), (0, 0)))
        out = part if out is None else out | part
    return out


def _unpack_words(words, a, lo, hi):
    """The inverse of _pack_words: [(first row, rows of `a`'s dtype)]
    held by the word rows [lo, hi) of `a` given as `words`."""
    if a.dtype.itemsize == 4:
        return [(0, jax.lax.bitcast_convert_type(words, a.dtype))]
    out = []
    for shift, r0, r1 in _word_parts(a, lo, hi):
        part = (words[:r1 - r0] >> shift) & ((1 << 8 * a.dtype.itemsize) - 1)
        if a.dtype == jnp.bool_:
            part = part != 0
        else:
            part = jax.lax.bitcast_convert_type(
                part.astype(jnp.uint8 if a.dtype.itemsize == 1
                            else jnp.uint16), a.dtype)
        out.append((r0, part))
    return out


@contract.traced_pure
def _resort_rows(keys, bufs, gstate, row_state):
    """Stable re-sort of every per-row buffer (rows on the LAST axis) by
    `keys` (most significant first; ties keep their order).  One stable
    lax.sort of the keys and an iota gives the permutation `rel` (new
    position j holds old row rel[j]); every array that can be uint32
    word rows (_word_rows: the 32-bit arrays with one row a position,
    the narrow integers and bools of any height, the bin matrix among
    them, four uint8 rows a word) is packed into ONE stacked matrix
    that a single gather moves, so a row's state moves once; what is
    left (64-bit state, narrow floats, [K, N] class-wise scores) follows
    `rel` by a gather each.  Equal to argsort(stable=True) and a take
    per array, to the bit.  The bin matrix keeps its [F, N] narrow
    shape between dispatches: the packed words live inside the step.
    At 68.3M rows and F = 39 the one gather of 15 word rows takes
    1.87 s on the chip where the bins' own gather took 2.09 s and the
    five words' 1.21 (PERF.md section 6, PR 36).  (The same arrays as
    payload operands of the sort itself ran another 0.8 s a re-sort
    faster and compile a minute longer on a cold start: PR 28.)

    The stack is filled, and the gathered stack taken apart into the
    outputs, _BLOCK_COLS columns at a time, in place: beside the stack
    itself (15 words a row at F = 39) the step holds a block's worth of
    temporaries, not a second and third copy of the bin matrix (PERF.md
    section 6, PR 36).

    Keys shorter than the buffers (bag compaction: the static in-bag
    window [:m]) sort the window only and leave the out-of-bag tail
    where it is — the tail-stays-in-place invariant that
    _bag_arrange_body and grow_tree_bagged rely on (tail rows never
    enter histograms, so their clustering is irrelevant and moving them
    would be pure waste).

    `row_state` is the objective's make_row_state_fn: which leaves of
    `gstate` are per-row (they join `bufs`), and how the permuted state
    is rebuilt from them and the full-length `rel` (lambdarank remaps
    its doc_idx row positions through the inverse).  A _FilledRows (DART's
    leaf bank), the last of `bufs` where there is one, follows the same
    `rel` in its own groups (_carry_filled) and comes back as its array.
    Returns (permuted bufs, permuted gstate)."""
    rows, rebuild = row_state(gstate)
    bufs, banks = _split_filled(bufs)
    arrays = bufs + list(rows)
    n = arrays[0].shape[-1]
    m = keys[0].shape[0]
    rel = jax.lax.sort(tuple(keys) + (jnp.arange(m, dtype=jnp.int32),),
                       num_keys=len(keys), is_stable=True)[len(keys)]

    # (array, its word rows [lo, hi)) in groups of at most _STACK_ROWS
    groups, room = [[]], _STACK_ROWS
    for i, a in enumerate(arrays):
        lo, total = 0, _word_rows(a, n)
        while lo < total:
            if not room:
                groups.append([])
                room = _STACK_ROWS
            hi = min(total, lo + room)
            groups[-1].append((i, lo, hi))
            room -= hi - lo
            lo = hi
    moved = [a.reshape(-1, n) if _moves_as_word(a, n)
             else jnp.take(a[..., :m], rel, axis=-1) if m == n
             else jnp.concatenate([jnp.take(a[..., :m], rel, axis=-1),
                                   a[..., m:]], axis=-1)
             for a in arrays]
    cols = min(m, _BLOCK_COLS)
    blocks = -(-m // cols)

    def start_of(b):        # the last block overlaps the one before it
        return jnp.minimum(b * cols, m - cols)

    # the row order is among the arrays at every site: never no group
    for group in groups:
        rows = sum(hi - lo for _, lo, hi in group)

        def fill(b, stack, group=group):
            start = start_of(b)
            return jax.lax.dynamic_update_slice(stack, jnp.concatenate(
                [_pack_words(moved[i], lo, hi, start, cols)
                 for i, lo, hi in group]), (0, start))

        stack = jax.lax.fori_loop(0, blocks, fill,
                                  jnp.zeros((rows, m), jnp.uint32))

        def spread(b, outs, group=group, stack=stack):
            start = start_of(b)
            words = jnp.take(stack, jax.lax.dynamic_slice(
                rel, (start,), (cols,)), axis=1)
            at = 0
            for i, lo, hi in group:
                for row, part in _unpack_words(words[at:at + hi - lo],
                                               moved[i], lo, hi):
                    outs[i] = jax.lax.dynamic_update_slice(
                        outs[i], part, (row, start))
                at += hi - lo
            return outs

        for i, out in jax.lax.fori_loop(
                0, blocks, spread,
                {i: moved[i] for i, _, _ in group}).items():
            moved[i] = out
    moved = [w.reshape(a.shape) for w, a in zip(moved, arrays)]
    carried = [_carry_filled(b, rel, cols, blocks, start_of) for b in banks]
    if m < n:
        rel = jnp.concatenate([rel, jnp.arange(m, n, dtype=jnp.int32)])
    return moved[:len(bufs)] + carried, rebuild(moved[len(bufs):], rel)


def _carry_filled(bank: _FilledRows, rel, cols, blocks, start_of):
    """The filled groups of DART's leaf bank through a re-sort's `rel`
    (the window's [m]; columns past it stay): per group the stack of its
    word rows is filled, gathered and taken apart into the bank IN PLACE,
    a block of `cols` columns at a time as _resort_rows does for its own
    stack, so that a re-sort holds one group's stack beside the bank
    (8 x 4 B a row) and never a copy of it.  The trip count is
    ceil(fill / group): what a re-sort moves of the bank follows what the
    job has banked so far, under one executable."""
    out, fill = bank
    group, words = _bank_group(out)
    bits = 8 * out.dtype.itemsize
    narrow = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}[bits]
    m = rel.shape[0]

    def one_group(g, out):
        def row_of(j, start):   # byte j of the group's words: these rows
            return (g * group + j * words).astype(start.dtype)

        def fill_block(b, stack):
            start = start_of(b)
            word = None
            for j in range(32 // bits):
                part = jax.lax.bitcast_convert_type(jax.lax.dynamic_slice(
                    out, (row_of(j, start), start), (words, cols)), narrow)
                part = part.astype(jnp.uint32) << (bits * j)
                word = part if word is None else word | part
            return jax.lax.dynamic_update_slice(stack, word, (0, start))

        stack = jax.lax.fori_loop(0, blocks, fill_block,
                                  jnp.zeros((words, m), jnp.uint32))

        def spread(b, out):
            start = start_of(b)
            got = jnp.take(stack, jax.lax.dynamic_slice(
                rel, (start,), (cols,)), axis=1)
            for j in range(32 // bits):
                part = (got >> (bits * j)) & ((1 << bits) - 1) \
                    if bits < 32 else got
                out = jax.lax.dynamic_update_slice(
                    out, jax.lax.bitcast_convert_type(
                        part.astype(narrow), out.dtype),
                    (row_of(j, start), start))
            return out

        return jax.lax.fori_loop(0, blocks, spread, out)

    with jax.named_scope(spans.DART_CARRY):
        return jax.lax.fori_loop(0, -(-fill // group), one_group, out)


def _packed_leaf_ids(row, bins, L):
    """Leaf ids of the packed tree `row` (_pack_tree's int row) over the
    rows of `bins` as they lie, by a replay of its splits: one pass over a
    bin row a split.  A row of zeros (no tree) gives every row leaf 0."""
    SF0, TB0, LC0, RC0 = _dart_layout(L)[:4]
    # + the dummy slot the replay skips
    return replay_leaf_binned_inline(
        jnp.pad(row[SF0:TB0], (0, 1)), jnp.pad(row[TB0:LC0], (0, 1)),
        jnp.pad(row[LC0:RC0], (0, 1), constant_values=-1), row[0], bins,
        jnp.uint8 if L <= 256 else jnp.int32)


# the trees grown before a re-sorting step's own whose leaves order the rows
# inside each of its leaves (_resort_by_leaf)
_RESORT_PREV = 2


def _leaf_key(leaf_ids, max_leaves: int, bits: int = 32):
    """ONE uint32 sort key a row from leaf ids, the most significant first,
    b bits each (b = the bits of max_leaves - 1, so every leaf fits); ids
    that would not fit in the key's low `bits` bits are left out.  None
    where no id is given."""
    b = (max_leaves - 1).bit_length()
    key = None
    for ids in leaf_ids[:bits // b]:
        ids = ids.astype(jnp.uint32)
        key = ids if key is None else (key << b) | ids
    return key


# key words a class-wise re-sort sorts beside its iota: a lax.sort compiles
# in time that grows with its operands (PERF.md section 7, row 9)
_CLASS_KEY_WORDS = 2


def _class_key(leaf_k, m: int, max_leaves: int):
    """The class-wise step's re-sort key: the K classes' leaf ids over the
    first `m` rows, class 0 most significant, packed by _leaf_key into at
    most _CLASS_KEY_WORDS uint32 words, 32 // b classes a word.  Sorted
    with an iota, it orders the rows exactly as the K ids as K keys would
    while K x b <= 64 (each id fits its b bits); classes past the words
    are left out, and their rows keep the order they had."""
    b = (max_leaves - 1).bit_length()
    per = 32 // b
    classes = min(leaf_k.shape[0], per * _CLASS_KEY_WORDS)
    with jax.named_scope(spans.CLASS_KEY):
        return tuple(_leaf_key([leaf_k[k, :m] for k in
                                range(lo, min(lo + per, classes))],
                               max_leaves)
                     for lo in range(0, classes, per))


@contract.traced_pure
def _resort_by_leaf(leaf_id, prev_trees, bufs, gstate, row_state,
                    compact_rows, max_leaves: int):
    """The re-sort that ends a re-sorting step, the plain one's and
    DART's alike: every per-row buffer (`bufs`: bins first; DART's leaf
    bank, a _FilledRows, last) and the objective's state, stably sorted
    by the tree's leaves and, inside a leaf, by the leaves of the trees
    grown just before it (`prev_trees`: their packed int rows, the latest
    first), whose ids a replay of their splits gives (_packed_leaf_ids).
    A later tree splits on the features the last ones split on, so it
    cuts these runs far less often than the order an older re-sort left
    inside a leaf (PERF.md section 6).  Ties keep that order.
    Padded rows ride along via their tracked leaf_id and stay
    permanently out-of-bag through the permuted bag mask.  Under bag
    compaction only the static window re-sorts."""
    n = bufs[0].shape[1]
    with jax.named_scope(spans.RESORT):
        m = compact_rows if 0 < compact_rows < n else n
        bins = bufs[0][:, :m]
        key = _leaf_key([leaf_id[:m]] + [_packed_leaf_ids(r, bins, max_leaves)
                                          for r in prev_trees], max_leaves)
        return _resort_rows((key,), bufs, gstate, row_state)


@contract.traced_pure
def _fused_step_body_reorder(grad_fn, grow_kw, lr, dtype, row_state,
                             compact_rows=0):
    """The fused step PLUS the ordered-partition row re-sort: after the
    tree lands, rows are stably re-sorted by its leaf assignment, and by
    the last trees' inside a leaf (`prev_trees`: their packed int rows,
    the latest first; the step returns them shifted by its own, which is
    what the next iteration of a scan takes), so later trees' leaves stay
    block-clustered and the block-list sweeps (ops/grow.py ranged mode)
    touch few blocks (_resort_by_leaf).  Everything per-row
    (bins, scores, bag mask, objective state, the composed row order)
    comes back permuted in the SAME dispatch (_resort_rows); valid sets
    and tree output are row-order-free.

    `row_state` is the objective's make_row_state_fn (how its
    grad_state follows the permutation).

    `compact_rows` (bag compaction): only the static in-bag window
    re-sorts — its work scales with the bag, and the out-of-bag tail
    keeps its positions (tail rows never enter histograms, so their
    clustering is irrelevant)."""
    def step(scores, valid_scores, bag_mask, fmask, bins, valid_bins,
             gstate, row_order, stopped, prev_trees):
        bag = _unpack_bag(bag_mask, bins.shape[1])
        with jax.named_scope(spans.OBJECTIVE):
            grad, hess = grad_fn(scores[0], gstate)
            grad, hess = grad.astype(dtype), hess.astype(dtype)
        with jax.named_scope(spans.GROW):
            dev_tree, leaf_id = grow_tree_bagged(
                bins, grad, hess, bag, fmask, bag_rows=compact_rows,
                **grow_kw)
        live = jnp.logical_not(stopped)
        stopped = stopped | (dev_tree.num_leaves <= 1)
        with jax.named_scope(spans.SCORE_UPDATE):
            leaf_vals = jnp.where(live, dev_tree.leaf_value * lr,
                                  0.0).astype(jnp.float32)
            scores = scores.at[0].add(leaf_vals[leaf_id])
        new_valid = []
        with jax.named_scope(spans.VALID_UPDATE):
            for vs, vbins in zip(valid_scores, valid_bins):
                vleaf = predict_leaf_binned(
                    dev_tree.split_feature, dev_tree.threshold_bin,
                    dev_tree.left_child, dev_tree.right_child, vbins)
                new_valid.append(vs.at[0].add(leaf_vals[vleaf]))
        with jax.named_scope(spans.PACK_TREE):
            ints, floats = _pack_tree(dev_tree)
        (bins_new, scores, bag_new, order_new), gstate_new = \
            _resort_by_leaf(leaf_id, prev_trees,
                            [bins, scores, bag, row_order], gstate,
                            row_state, compact_rows, grow_kw["max_leaves"])
        return (scores, new_valid, ints, floats, bins_new, bag_new,
                gstate_new, order_new, stopped,
                (ints,) + tuple(prev_trees[:-1]))
    return step


@contract.traced_pure
@contract.fused_body(extras=("order",),
                     collectives=("all_gather", "axis_index", "psum",
                                  "psum_scatter"))
def _make_fused_step_reorder(grad_fn, grow_kw, lr, dtype, row_state,
                             compact_rows=0, k_iters=1):
    # gstate is NOT donated: on the first re-sort it aliases the
    # objective's own arrays, which must stay valid for metrics/restarts
    body = _batch_iters(_fused_step_body_reorder(grad_fn, grow_kw, lr,
                                                 dtype, row_state,
                                                 compact_rows),
                        _SCAN_REORDER, k_iters)
    return jax.jit(body, donate_argnums=(0, 1, 2, 4, 7))


def _packed_ints(L):
    """The length of _pack_tree's int row at L leaves."""
    return 1 + 4 * (L - 1) + 3 * L + 4


def _dart_layout(L):
    """Packed-row slice offsets for the DART device bank (the _pack_tree
    wire layout): int row [1 | sf | tb | lc | rc | lp | ld | lcnt],
    float row [sg | leaf_value | iv]."""
    SF0 = 1
    TB0 = SF0 + (L - 1)
    LC0 = TB0 + (L - 1)
    RC0 = LC0 + (L - 1)
    RC1 = RC0 + (L - 1)
    LV0, LV1 = L - 1, 2 * L - 1
    return SF0, TB0, LC0, RC0, RC1, LV0, LV1


def _bank_row(bank, j):
    """Row j of a leaf bank as int32 ids in an [N] array of its own.  A
    look-up of a small table by ids that the same fusion slices out of
    the tiled [T, N] bank is emitted as one pass over the rows a LEAF
    (0.22 s a dropped tree at 68.3M rows and 63 leaves, where the row
    taken out first costs 3.5 ms: PERF.md section 6, PR 37)."""
    return jax.lax.optimization_barrier(bank[j]).astype(jnp.int32)


def _dart_replayed_ids(bank_i, j, bins, L):
    """Leaf ids of tree j over the rows as they lie, from its splits in
    the tree bank: what the leaf bank would hold had it room for j."""
    with jax.named_scope(spans.DART_REPLAY):
        return _packed_leaf_ids(bank_i[j], bins, L)


@contract.traced_pure
def _dart_drop_trees(scores, bank_f, bank_i, leaf_bank, bins, drop_idx,
                     drops, L, replay_slots):
    """DART's drop phase (dart.hpp:86-110) over the device bank: for each
    of the first `drops` trees of `drop_idx`, shrinkage(-1) persisted in
    bank_f and the train-score add.  A tree inside the leaf bank (all
    but its last row) adds by its cached ids; one past it by a replay of
    its splits, whose ids are kept for the normalise in one of
    `replay_slots` [N] buffers while there is one left.  ONE program
    whether the bank holds every tree or not: the same adds in the same
    order, so a bounded bank changes no bit of a job
    (tests/test_dart_bank.py).  -> (scores, bank_f, kept)."""
    SF0, TB0, LC0, RC0, RC1, LV0, LV1 = _dart_layout(L)
    bank_cap = leaf_bank.shape[0] - 1

    def drop_body(i, carry):
        sc, bf, kept, outside = carry
        j = drop_idx[i]
        v1 = -bf[j, LV0:LV1]
        add = v1.astype(jnp.float32)

        def banked(sc, kept, outside):
            return (sc.at[0].add(add[_bank_row(leaf_bank, j)]),
                    kept, outside)

        def replayed(sc, kept, outside):
            leaf = _dart_replayed_ids(bank_i, j, bins, L)
            kept = tuple(jnp.where(outside == s,
                                   leaf.astype(leaf_bank.dtype), k)
                         for s, k in enumerate(kept))
            return sc.at[0].add(add[leaf]), kept, outside + 1

        sc, kept, outside = jax.lax.cond(j < bank_cap, banked, replayed,
                                         sc, kept, outside)
        return sc, bf.at[j, LV0:LV1].set(v1), kept, outside

    with jax.named_scope(spans.DART_DROP):
        kept = tuple(jnp.zeros(bins.shape[1], leaf_bank.dtype)
                     for _ in range(replay_slots))
        scores, bank_f, kept, _ = jax.lax.fori_loop(
            0, drops, drop_body, (scores, bank_f, kept, jnp.int32(0)))
    return scores, bank_f, kept


@contract.traced_pure
def _dart_normalize_trees(scores, vss, bank_f, bank_i, leaf_bank, vbanks,
                          bins, drop_idx, drops, lr, kf, kept, L):
    """DART's normalise (dart.hpp:114-129) over the device bank: per
    dropped tree shrinkage(rate) + VALID add, then shrinkage(-k) + TRAIN
    add, both persisted in bank_f.  `kept`: the ids the drop phase
    replayed, in its order; a replayed tree past them is replayed again.
    -> (scores, vss, bank_f)."""
    SF0, TB0, LC0, RC0, RC1, LV0, LV1 = _dart_layout(L)
    bank_cap = leaf_bank.shape[0] - 1

    def norm_body(i, carry):
        sc, vss, bf, outside = carry
        j = drop_idx[i]
        v2 = bf[j, LV0:LV1] * lr
        vss = tuple(
            vs.at[0].add(v2.astype(jnp.float32)[_bank_row(vb, j)])
            for vs, vb in zip(vss, vbanks))
        v3 = v2 * (-kf)
        add = v3.astype(jnp.float32)

        def banked(sc, outside):
            return (sc.at[0].add(add[_bank_row(leaf_bank, j)]),
                    outside)

        def kept_ids(sc):
            ids = kept[0]
            for s in range(1, len(kept)):
                ids = jnp.where(outside == s, kept[s], ids)
            return sc.at[0].add(add[ids.astype(jnp.int32)])

        def again(sc):
            return sc.at[0].add(add[_dart_replayed_ids(bank_i, j, bins, L)])

        def replayed(sc, outside):
            sc = (jax.lax.cond(outside < len(kept), kept_ids, again, sc)
                  if kept else again(sc))
            return sc, outside + 1

        sc, outside = jax.lax.cond(j < bank_cap, banked, replayed, sc,
                                   outside)
        return sc, vss, bf.at[j, LV0:LV1].set(v3), outside

    with jax.named_scope(spans.DART_NORMALIZE):
        scores, vss, bank_f, _ = jax.lax.fori_loop(
            0, drops, norm_body, (scores, tuple(vss), bank_f, jnp.int32(0)))
    return scores, vss, bank_f


@functools.partial(jax.jit, donate_argnums=0)
def _set_bank_row(bank, t, ids):
    """One row of DART's leaf bank, in place (a restore's rebuild)."""
    return bank.at[t].set(ids.astype(bank.dtype))


@contract.traced_pure
@contract.fused_body(extras=("bank", "dart", "order"),
                     collectives=("all_gather", "axis_index", "psum",
                                  "psum_scatter"))
def _make_fused_step_dart(grad_fn, grow_kw, dtype, max_leaves,
                          replay_slots=0, row_state=None,
                          compact_rows=0, k_iters=1):
    """Fused DART iteration over a DEVICE-RESIDENT tree bank (VERDICT r3
    weak #5: DART previously paid ~6 host dispatches + a blocking tree
    flush per iteration for its drop/normalize score surgery).  The bank
    holds every trained tree's packed int/float rows on device; one
    dispatch per iteration performs, in the reference's exact order
    (dart.hpp:86-129):

      1. drop phase — for each dropped tree (ascending): shrinkage(-1)
         persisted in the bank + train-score add;
      2. gradients from the dropped scores, grow the new tree with the
         iteration's 1/(1+k) shrinkage (a TRACED scalar, so every drop
         count shares this executable), score/valid updates, bank append;
      3. normalize — per dropped tree: shrinkage(rate) + VALID add, then
         shrinkage(-k) + TRAIN add, both persisted.

    The in-bank value mutations run in the histogram dtype: bit-exact
    under the float64 parity configuration; under f32 they feed SCORE
    updates only within the usual f32-ulp policy — the MODEL's leaf
    values are reproduced on the host by replaying each tree's recorded
    drop-factor chain in float64 (DART._materialize_bank), exactly the
    host/reference tree->Shrinkage sequence, so long drop histories
    cannot drift the saved model.

    The drop list has the bank's length and a RUN-TIME count
    (`drop_count`: the trip count of the drop and normalise loops), so
    one executable serves every drop count (a shape-per-count design
    measured 3 mid-loop recompiles per bench run; a cap of 8 with
    power-of-two buckets past it compiled again wherever a late
    iteration dropped 9).  The device `stopped` flag zeroes the count,
    so deferred host flushes truncate at the exact reference stop point.

    Leaf assignments are CACHED per tree (leaf_bank / per-valid-set
    vbanks) at training time: tree structure never changes after
    training, so the drop/normalize adds gather a [L] value table by the
    cached ids instead of re-descending every row per dropped tree —
    the descent's per-level [N] gathers measured ~6x the gather-only
    cost on TPU (r3 memory: gathers dominate; reformulate).  The leaf
    bank holds the first rows - 1 trees only (its last row is the one
    dead and unbanked steps write to; DART._plan_bank sizes it from the
    device's memory): a dropped tree past it gets its ids by a replay of
    its splits, which bank_i holds for every tree (replay_leaf_binned:
    a pass over a bin row a split), kept for the normalise in one of
    `replay_slots` [N] buffers where there is one left, else replayed
    again there.

    `row_state` (the objective's make_row_state_fn) makes it the
    RE-SORTING step: after the normalise every per-row buffer, the
    leaf bank's filled groups among them, is stably sorted by the new
    tree's leaves (_resort_by_leaf, the plain re-sort step's tail)."""
    L = max_leaves
    SF0, TB0, LC0, RC0, RC1, LV0, LV1 = _dart_layout(L)
    reorder = row_state is not None

    def step(scores, valid_scores, bank_i, bank_f, leaf_bank, vbanks,
             drop_idx, drop_count, lr, kf, bag_mask, fmask, bins,
             valid_bins, gstate, stopped, t_row, *row_order):
        live = jnp.logical_not(stopped)
        drops = jnp.where(live, drop_count, 0)
        bank_cap = leaf_bank.shape[0] - 1
        scores, bank_f, kept = _dart_drop_trees(
            scores, bank_f, bank_i, leaf_bank, bins, drop_idx, drops, L,
            replay_slots)

        bag = _unpack_bag(bag_mask, bins.shape[1])
        with jax.named_scope(spans.OBJECTIVE):
            grad, hess = grad_fn(scores[0], gstate)
            grad, hess = grad.astype(dtype), hess.astype(dtype)
        with jax.named_scope(spans.GROW):
            dev_tree, leaf_id = grow_tree_bagged(
                bins, grad, hess, bag, fmask, bag_rows=compact_rows,
                **grow_kw)
        stopped = stopped | (dev_tree.num_leaves <= 1)
        with jax.named_scope(spans.SCORE_UPDATE):
            leaf_vals = jnp.where(live, dev_tree.leaf_value * lr,
                                  0.0).astype(jnp.float32)
            scores = scores.at[0].add(leaf_vals[leaf_id])
        wrow = jnp.where(live, t_row, bank_i.shape[0] - 1)  # dead -> dummy
        new_valid = []
        new_vbanks = []
        for vs, vbins, vb in zip(valid_scores, valid_bins, vbanks):
            with jax.named_scope(spans.VALID_UPDATE):
                vleaf = predict_leaf_binned(
                    dev_tree.split_feature, dev_tree.threshold_bin,
                    dev_tree.left_child, dev_tree.right_child, vbins)
                new_valid.append(vs.at[0].add(leaf_vals[vleaf]))
            with jax.named_scope(spans.DART_BANK):
                new_vbanks.append(vb.at[wrow].set(
                    vleaf.astype(leaf_bank.dtype)))
        with jax.named_scope(spans.PACK_TREE):
            ints, floats = _pack_tree(dev_tree)
        # the bank row holds the tree's CURRENT (shrunk) leaf values,
        # like the reference's in-memory trees; the RETURNED floats stay
        # raw — the host applies the iteration's shrinkage in f64 like
        # every other fused path, so materialized models carry no extra
        # device-dtype rounding
        with jax.named_scope(spans.DART_BANK):
            bank_row_f = floats.at[LV0:LV1].set(
                dev_tree.leaf_value[:-1] * lr)
            bank_i = bank_i.at[wrow].set(ints)
            bank_f = bank_f.at[wrow].set(bank_row_f)
            # a tree past the leaf bank's last real row is not banked
            leaf_bank = leaf_bank.at[
                jnp.where(live, jnp.minimum(t_row, bank_cap),
                          bank_cap)].set(leaf_id.astype(leaf_bank.dtype))

        scores, vss, bank_f = _dart_normalize_trees(
            scores, tuple(new_valid), bank_f, bank_i, leaf_bank,
            new_vbanks, bins, drop_idx, drops, lr, kf, kept, L)
        # ints/floats (the AS-TRAINED packed tree, before any later drop
        # mutation) also return to the host: materialization needs the
        # pristine values for the f64 factor replay, with no bank pull
        out = (scores, list(vss), bank_i, bank_f, leaf_bank,
               list(new_vbanks), ints, floats, stopped)
        if not reorder:
            return out
        filled = _FilledRows(leaf_bank, jnp.minimum(t_row + 1, bank_cap))
        # the trees before this one are the tree bank's rows before its
        # (zeros, no tree, before the first)
        prev_trees = [jnp.where(t_row >= j, bank_i[jnp.maximum(t_row - j, 0)],
                                0) for j in range(1, _RESORT_PREV + 1)]
        (bins_new, scores, bag_new, order_new, leaf_bank), gstate_new = \
            _resort_by_leaf(leaf_id, prev_trees,
                            [bins, scores, bag, row_order[0], filled],
                            gstate, row_state, compact_rows, L)
        return ((scores,) + out[1:4] + (leaf_bank,) + out[5:]
                + (bins_new, bag_new, gstate_new, order_new))
    # gstate is NOT donated (the first re-sort aliases the objective's
    # own arrays); the re-sorting step replaces bag, bins and the order
    return jax.jit(_batch_iters(step, _SCAN_DART_REORDER if reorder
                                else _SCAN_DART, k_iters),
                   donate_argnums=(0, 1, 2, 3, 4, 5)
                   + ((10, 12, 17) if reorder else ()))


@contract.traced_pure
def _fused_step_multi_body(grad_fn, grow_kw, lr, dtype, reorder,
                           row_state, compact_rows=0):
    """Fused MULTICLASS iteration (VERDICT r3 #4): gradients for all K
    classes from the pre-iteration scores, then a class-wise lax.scan
    grows the K per-iteration trees in ONE dispatch — the reference's
    per-class tree loop (gbdt.cpp:177-197) without K host round trips or
    the per-iteration flush.  The scanned `stopped` flag no-ops score
    updates after the first 1-leaf stump (including LATER CLASSES of the
    same iteration), so a deferred host flush truncates at the exact
    reference stop point with scores untouched past it — the multiclass
    extension of the single-class deferral argument.

    bag_masks [K, N] bool and fmasks [K, F] bool are per-class (each
    class draws its own mt19937 masks, one TreeLearner per class in the
    reference, gbdt.cpp:38-45).

    `reorder` (round 4) extends the ordered-partition growth to
    multiclass with ONE shared row order sorted by the JOINT leaf key —
    a stable lexicographic sort over all K of this iteration's leaf
    assignments.  The K trees differ, but they are correlated (they
    model the same data), so the joint cells are homogeneous in EVERY
    class: measured at the 1M x 28 bench, the joint order cuts
    block-sweeps ~10x for every class — better even than giving each
    class its own order, and it needs no per-iteration gathers (a
    per-class-orders prototype spent more on [F, N] gathers than the
    clustered sweeps saved; gathers run ~100x off HBM bandwidth on
    TPU).  All per-row state (scores [K, N], bins, bag masks, the
    objective's onehot/weights, the composed row order) permutes in
    the SAME dispatch, exactly like the single-class reorder step."""
    def step(scores, valid_scores, bag_masks, fmasks, bins, valid_bins,
             gstate, stopped, *row_order):
        with jax.named_scope(spans.OBJECTIVE):
            grad, hess = grad_fn(scores, gstate)        # [K, N] each
        num_class = grad.shape[0]

        def body(carry, xs):
            sc, vss, stop = carry
            cls, g, h, bag, fm = xs
            with jax.named_scope(spans.OBJECTIVE):
                g, h = g.astype(dtype), h.astype(dtype)
            with jax.named_scope(spans.GROW):
                dev_tree, leaf_id = grow_tree_bagged(
                    bins, g, h, bag, fm, bag_rows=compact_rows, **grow_kw)
            live = jnp.logical_not(stop)
            stop = stop | (dev_tree.num_leaves <= 1)
            with jax.named_scope(spans.SCORE_UPDATE):
                leaf_vals = jnp.where(live, dev_tree.leaf_value * lr,
                                      0.0).astype(jnp.float32)
                sc = sc.at[cls].add(leaf_vals[leaf_id])
            new_vss = []
            with jax.named_scope(spans.VALID_UPDATE):
                for vs, vbins in zip(vss, valid_bins):
                    vleaf = predict_leaf_binned(
                        dev_tree.split_feature, dev_tree.threshold_bin,
                        dev_tree.left_child, dev_tree.right_child, vbins)
                    new_vss.append(vs.at[cls].add(leaf_vals[vleaf]))
            with jax.named_scope(spans.PACK_TREE):
                ints, floats = _pack_tree(dev_tree)
            ys = ((ints, floats, leaf_id) if reorder else (ints, floats))
            return (sc, tuple(new_vss), stop), ys

        (scores, vss, stopped), ys = jax.lax.scan(
            body, (scores, tuple(valid_scores), stopped),
            (jnp.arange(num_class, dtype=jnp.int32), grad, hess,
             bag_masks, fmasks))
        if not reorder:
            ints_k, floats_k = ys
            return scores, list(vss), ints_k, floats_k, stopped
        ints_k, floats_k, leaf_k = ys                   # leaf_k [K, N]
        # stable lexicographic sort, class 0 primary, by the K leaf
        # assignments packed into at most two key words (_class_key).
        # Under bag compaction only the static union window re-sorts;
        # the OOB tail keeps its positions (it never enters histograms)
        with jax.named_scope(spans.RESORT):
            n = bins.shape[1]
            m = compact_rows if 0 < compact_rows < n else n
            (bins_new, scores, bag_new, order_new), gstate_new = \
                _resort_rows(_class_key(leaf_k, m, grow_kw["max_leaves"]),
                             [bins, scores, bag_masks, row_order[0]],
                             gstate, row_state)
        return (scores, list(vss), ints_k, floats_k, stopped,
                bins_new, bag_new, gstate_new, order_new)
    return step


@contract.traced_pure
@contract.fused_body(extras=("order",),
                     collectives=("all_gather", "axis_index", "psum",
                                  "psum_scatter"))
def _make_fused_step_multi(grad_fn, grow_kw, lr, dtype, reorder,
                           row_state, compact_rows=0, k_iters=1):
    # gstate is NOT donated: on the first re-sort it aliases the
    # objective's own arrays (same constraint as the single-class
    # reorder step)
    body = _batch_iters(
        _fused_step_multi_body(grad_fn, grow_kw, lr, dtype, reorder,
                               row_state, compact_rows),
        _SCAN_MULTI_REORDER if reorder else _SCAN_MULTI, k_iters)
    return jax.jit(body,
                   donate_argnums=(0, 1, 2, 4, 8) if reorder else (0, 1))


@contract.traced_pure
@contract.fused_body(extras=("order",),
                     collectives=("all_gather", "axis_index", "psum",
                                  "psum_scatter"))
def _make_fused_step_multi_sharded(grad_fn, grow_kw, lr, dtype, mesh,
                                   n_valid, gstate_specs, reorder,
                                   row_state, compact_rows=0,
                                   k_iters=1):
    """The multiclass fused step under shard_map for single-host
    tree_learner=data (VERDICT r4 #3): the class-wise scan body already
    threads psum_axis through grow_kw, so sharding it is the same
    transform as the single-class _make_fused_step_sharded — per-row
    state ([K, N] scores/bag masks, bins, gradient state, row order)
    shards along the data axis, valid sets and the K packed trees are
    replicated, and the joint-leaf-key re-sort stays SHARD-LOCAL."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, shard_map

    # the scan wraps the BODY, inside shard_map: each shard iterates its
    # rows through the K steps, collectives stay per-step, and the
    # replicated specs (P()) cover the [K, ...] xs/ys at any rank
    body = _batch_iters(
        _fused_step_multi_body(grad_fn, grow_kw, lr, dtype, reorder,
                               row_state, compact_rows),
        _SCAN_MULTI_REORDER if reorder else _SCAN_MULTI, k_iters)
    row = P(DATA_AXIS)
    row2 = P(None, DATA_AXIS)
    rep = P()
    vrep = [rep] * n_valid
    common_in = (row2, vrep, row2, rep, row2, tuple(vrep), gstate_specs,
                 rep)
    if reorder:
        in_specs = common_in + (row,)
        out_specs = (row2, vrep, rep, rep, rep, row2, row2, gstate_specs,
                     row)
        donate = (0, 1, 2, 4, 8)
    else:
        in_specs = common_in
        out_specs = (row2, vrep, rep, rep, rep)
        donate = (0, 1)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs)
    return jax.jit(fn, donate_argnums=donate)


@contract.traced_pure
@contract.fused_body(extras=("order",),
                     collectives=("all_gather", "axis_index", "psum",
                                  "psum_scatter"))
def _make_fused_step_sharded(grad_fn, grow_kw, lr, dtype, mesh,
                             n_valid, gstate_specs, reorder,
                             row_state, compact_rows=0,
                             k_iters=1):
    """The fused step under shard_map for single-host tree_learner=data
    (VERDICT r3 #2): per-row state (scores row, bins, bag mask, gradient
    state, row order) shards along the data axis, valid sets and tree
    outputs are replicated, and the ordered-partition re-sort — when
    `reorder` — stays SHARD-LOCAL (each shard leaf-clusters its own
    rows; grow_tree's psum'd histograms are order-invariant within a
    shard, so the tree is identical to the unordered sharded tree).

    Multi-host keeps the general path: its per-row state is process-
    local and reassembled per tree (models/gbdt.py _train_tree)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, shard_map

    body = (_batch_iters(_fused_step_body_reorder(grad_fn, grow_kw, lr,
                                                  dtype, row_state,
                                                  compact_rows),
                         _SCAN_REORDER, k_iters)
            if reorder
            else _batch_iters(_fused_step_body(grad_fn, grow_kw, lr,
                                               dtype, compact_rows),
                              _SCAN_PLAIN, k_iters))
    row = P(DATA_AXIS)
    row2 = P(None, DATA_AXIS)
    rep = P()
    vrep = [rep] * n_valid
    common_in = (row2, vrep, row, rep, row2, tuple(vrep), gstate_specs)
    if reorder:
        in_specs = common_in + (row, rep, rep)
        out_specs = (row2, vrep, rep, rep, row2, row, gstate_specs,
                     row, rep, rep)
        donate = (0, 1, 2, 4, 7)
    else:
        in_specs = common_in + (rep,)
        out_specs = (row2, vrep, rep, rep, rep)
        donate = (0, 1)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs)
    return jax.jit(fn, donate_argnums=donate)


@contract.traced_pure
def _bag_arrange_body(row_state, multi, max_leaves):
    """The bag-compaction boundary step, ONE dispatch per re-bagging: a
    stable arrangement of every per-row device buffer, in-bag rows first,
    and on both sides of that line in leaf order.  The sort key is ONE
    uint32 a row: the out-of-bag bit at the top and below it, packed by
    _leaf_key into 31 bits, the leaf ids of the trees grown last (`prev`:
    their packed int rows, the latest first, replayed over every row by
    _packed_leaf_ids, as a re-sort replays them).  So the rows that enter
    the bag at a redraw land in their leaves' runs instead of at the
    window's end in the tail's old order, and the window leaves the
    arrangement as a re-sort would leave it (PERF.md section 6).
    Rows of zeros (no tree yet) put every row in leaf 0: the plain
    in-bag-first partition, to the bit.  The arrangement is a plain row
    permutation, so it composes with the ordered-partition machinery: the
    permuted `order` rides the same composed row order that metrics
    inversion, checkpointing and the general-path restore already
    understand.  Multiclass passes no trees and sorts by the UNION of the
    per-class masks alone (the static window bounds the union; each class
    still masks its own rows inside it)."""
    def arrange(bins, scores, mask, gstate, order, prev, *bank):
        with jax.named_scope(spans.BAG_ARRANGE):
            out_of_bag = jnp.logical_not(mask.any(axis=0) if multi
                                         else mask)
            key = _leaf_key([_packed_leaf_ids(r, bins, max_leaves)
                             for r in prev], max_leaves, bits=31)
            key = (out_of_bag if key is None
                   else (out_of_bag.astype(jnp.uint32) << 31) | key)
            # DART's leaf bank is per-row on its last axis too: `bank`
            # is (its rows, how many are filled)
            filled = [_FilledRows(*bank)] if bank else []
            (bins, scores, mask, order, *moved), gstate = _resort_rows(
                (key,), [bins, scores, mask, order, *filled], gstate,
                row_state)
        return (bins, scores, mask, gstate, order, *moved)
    return arrange


def _make_bag_arrange(row_state, multi, with_bank, max_leaves):
    # gstate is NOT donated (first arrangement aliases the objective's
    # own arrays), nor are the trees of the key; everything else is
    # replaced by its permuted successor
    donate = (0, 1, 2, 4) + ((6,) if with_bank else ())
    return jax.jit(_bag_arrange_body(row_state, multi, max_leaves),
                   donate_argnums=donate)


def _make_bag_arrange_sharded(row_state, multi, mesh, gstate_specs,
                              max_leaves):
    """The arrangement under shard_map: each shard sorts ITS OWN rows
    in-bag-first (rel is computed from the shard-local mask and the
    replicated trees' shard-local replay), so shard membership never
    changes and the grow step's psum invariants hold — every in-bag row
    lands in exactly one shard's static window."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, shard_map

    body = _bag_arrange_body(row_state, multi, max_leaves)
    row = P(DATA_AXIS)
    row2 = P(None, DATA_AXIS)
    mspec = row2 if multi else row
    specs = (row2, row2, mspec, gstate_specs, row)
    fn = shard_map(body, mesh=mesh, in_specs=specs + (P(),),
                   out_specs=specs)
    return jax.jit(fn, donate_argnums=(0, 1, 2, 4))


class GBDT:
    name = "gbdt"

    def __init__(self, config: Config, train_data: Optional[Dataset],
                 objective: Optional[Objective],
                 training_metrics: Sequence[Metric] = ()):
        rows = 0 if train_data is None else train_data.num_data
        with spans.startup(spans.STARTUP_BOOSTER, rows=rows) as stats:
            self._build(config, train_data, objective, training_metrics)
            stats.update(self._startup_stats())

    def _startup_stats(self) -> dict:
        """What the booster's start-up span says beyond its rows (DART:
        the leaf bank's bound)."""
        return {}

    def _build(self, config: Config, train_data: Optional[Dataset],
               objective: Optional[Objective],
               training_metrics: Sequence[Metric]) -> None:
        """All of __init__, inside its start-up span."""
        self.config = config
        self.train_data = train_data
        self.objective = objective
        self.num_class = config.num_class
        self.iter = 0
        self._models: List = []       # Tree | _PendingTree (see models prop)
        self._stopped = False
        self._fused_sharded = False
        self._mh_fused = False
        self._flush_every = 1   # recomputed below once bagging state is known
        self.num_used_model = 0
        self.early_stopping_round = config.early_stopping_round
        self.shrinkage_rate = config.learning_rate
        self.training_metrics = list(training_metrics)
        self.valid_data: List[Dataset] = []
        self.valid_metrics: List[List[Metric]] = []
        self.valid_bins_dev: List[jax.Array] = []
        self.valid_scores: List[jax.Array] = []
        self.best_iter: List[List[int]] = []
        self.best_score: List[List[float]] = []
        self.saved_upto = -1
        self._model_file = None

        # sigmoid only used for binary output transform (gbdt.cpp:60-65)
        self.sigmoid = -1.0
        if objective is not None and objective.name == "binary":
            self.sigmoid = config.sigmoid

        if train_data is None:
            self.max_feature_idx = 0
            self.label_idx = 0
            return

        n = train_data.num_data
        self.num_data = n
        self.max_feature_idx = train_data.num_total_features - 1
        self.label_idx = train_data.label_idx
        self.dtype = jnp.float64 if config.hist_dtype == "float64" else jnp.float32

        self.params = SplitParams(
            min_data_in_leaf=config.min_data_in_leaf,
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            lambda_l1=config.lambda_l1,
            lambda_l2=config.lambda_l2,
            min_gain_to_split=config.min_gain_to_split)
        self.max_bin = int(train_data.max_num_bin)

        # histogram implementation: the Pallas radix kernel is the TPU fast
        # path (f32, uint8 bins, <=256 bins); XLA one-hot elsewhere
        platform = jax.devices()[0].platform
        impl = config.hist_impl
        if impl == "auto":
            on_accel = platform != "cpu"
            impl = ("pallas" if (on_accel and self.max_bin <= 256
                                 and self.dtype == jnp.float32
                                 and train_data.bin_dtype == np.uint8)
                    else "xla")
            if on_accel and impl == "xla":
                # not silent: the parity configuration (hist_dtype=
                # float64) or wide bins forfeit the Pallas fast path
                log.warning(
                    "Histogram fast path (Pallas) disabled on this "
                    "accelerator (max_bin=%d, hist_dtype=%s, bins dtype "
                    "%s); using the slower XLA one-hot path"
                    % (self.max_bin, config.hist_dtype,
                       train_data.bin_dtype))
        self.hist_impl = impl
        row_unit = 1
        if impl == "pallas":
            # import lazily: XLA-only installs never touch Pallas
            from ..ops.hist_pallas import PALLAS_ROW_BLOCK
            if self.max_bin > 256:
                log.fatal("hist_impl=pallas requires max_bin <= 256 "
                          "(got %d); use hist_impl=xla" % self.max_bin)
            if self.dtype != jnp.float32:
                log.fatal("hist_impl=pallas accumulates in float32; "
                          "hist_dtype=%s is incompatible" % config.hist_dtype)
            if train_data.bin_dtype != np.uint8:
                log.fatal("hist_impl=pallas requires uint8 bins")
            row_unit = PALLAS_ROW_BLOCK
        log.info("Histograms: hist_impl=%s kernels=%s"
                 % (impl,
                    "xla" if impl != "pallas"
                    else "interpret" if platform == "cpu"
                    else "compiled"))

        # data-parallel: shard rows over a device mesh (parallel/mesh.py),
        # replacing the reference's socket/MPI histogram reduce-scatter.
        # Rows are padded so each shard's slice is a multiple of the Pallas
        # row block; padded rows are permanently out-of-bag.
        self.grower = None
        self.rows_sharded = False
        self._mh = False
        self._feat_mh = False
        row_unit_base = row_unit   # per-shard row alignment (Pallas block)
        self._row_unit_base = row_unit_base
        if config.tree_learner in ("data", "voting"):
            from ..parallel.mesh import ShardedGrower, make_mesh
            mesh = make_mesh(config.num_shards)
            self.grower = ShardedGrower(
                mesh, max_leaves=max(config.num_leaves, 2),
                max_bin=self.max_bin, params=self.params,
                max_depth=config.max_depth,
                voting_top_k=(config.top_k
                              if config.tree_learner == "voting" else 0),
                hist_impl=impl, hist_agg=config.hist_agg)
            row_unit *= self.grower.num_shards
            self.rows_sharded = True
            # multi-host: every process pads its LOCAL rows to the same
            # length (max local row count) so the global assembly via
            # make_array_from_process_local_data has equal blocks; all
            # other state (scores, objective, metrics, bagging) stays
            # process-local, matching the reference's locality (its
            # metrics/objectives never touch Network:: either)
            self._mh = jax.process_count() > 1
            if self._mh:
                from ..parallel.dist import process_allgather
                all_n = process_allgather(np.asarray([n], dtype=np.int64))
                self._n_pad_base = int(np.max(all_n))
        elif config.tree_learner == "feature":
            # multi-host feature parallel since round 3 (the reference's
            # multi-machine FeatureParallelTreeLearner): every process
            # loads ALL rows (cli.init_train forces row_shards=1), the
            # bin matrix splits along F across all hosts' devices, and
            # the best-split all-gather + argmax crosses hosts over DCN
            from ..parallel.mesh import (FeatureShardedGrower, make_mesh,
                                         FEATURE_AXIS)
            mesh = make_mesh(config.num_shards, FEATURE_AXIS)
            self.grower = FeatureShardedGrower(
                mesh, max_leaves=max(config.num_leaves, 2),
                max_bin=self.max_bin, params=self.params,
                max_depth=config.max_depth, hist_impl=impl)
            self._feat_mh = jax.process_count() > 1
        # bounded histogram working set (the reference HistogramPool's
        # role, feature_histogram.hpp:275-398): translate the MB budget
        # into a slot count of [F, max_bin, 3] leaf histograms for the
        # on-device LRU pool in ops/grow.py.  Parallel learners ignore it
        # (config already reset it, mirroring config.cpp:167-175).
        self.hist_slots = 0
        if config.histogram_pool_size >= 0 and self.grower is None:
            entry = (train_data.num_features * self.max_bin * 3
                     * np.dtype(self.dtype).itemsize)
            k = int(config.histogram_pool_size * 1024 * 1024
                    / max(entry, 1))
            k = max(2, k)   # smaller/larger pair minimum, like the pool's
            if k <= max(config.num_leaves, 2):
                self.hist_slots = k

        # tree_learner=data can run the fused step (and the ordered
        # partition below) under shard_map: every per-row array shards
        # along the data axis and re-sorts stay shard-local
        # (_make_fused_step_sharded).  Since round 5 this includes
        # MULTI-HOST (VERDICT r4 #2): per-row state is assembled into
        # global sharded arrays ONCE (scores/objective state/bag masks),
        # the fused dispatch keeps gradients on device, and per-iteration
        # host traffic drops to O(packed tree) — the per-tree
        # [N_local] grad/hess device->host->device round trip of the
        # general path is gone.  Multi-host additionally needs a
        # row-shardable traceable objective up front (the general path
        # cannot hand its local scores to a global fused step
        # mid-training, so the choice is made here, not per-iteration);
        # voting keeps the general path (its per-split protocol is
        # latency-bound anyway).
        mh_fusible = (type(self) is GBDT
                      and objective is not None
                      and getattr(objective, "jax_traceable", False)
                      and getattr(objective, "row_shardable", False)
                      and objective.fused_key() is not None)
        self._fused_sharded = (self.rows_sharded
                               and config.tree_learner == "data"
                               and (not self._mh or mh_fusible))
        self._mh_fused = self._mh and self._fused_sharded

        # query-granular row layout: an objective whose grad_state is
        # NOT per-row (lambdarank's query blocks) provides its own row
        # placement for the fused sharded step — shard s's contiguous
        # device block holds whole queries padded to a common capacity,
        # so each shard computes its queries' pairwise lambdas locally
        # and only histograms cross shards (the reference's rank + data-
        # parallel locality, data_parallel_tree_learner.cpp:124-187).
        # None for elementwise objectives (default contiguous blocks).
        self._shard_layout = None
        self._layout_active = False
        if (self._fused_sharded and config.tree_learner == "data"
                and objective is not None
                and getattr(objective, "jax_traceable", False)):
            # capacity alignment: the Pallas row block, times the
            # process count under multi-host so every process's local
            # block (cap * local_shards) divides over the GLOBAL device
            # count (shard_bins/_put_sharded equal-block requirement)
            align = row_unit_base * (jax.process_count() if self._mh
                                     else 1)
            self._shard_layout = objective.shard_layout(
                self.grower.local_shard_count(), align, self._mh)
            self._layout_active = self._shard_layout is not None
        self._gstate_specs = None

        if self._shard_layout is not None:
            # local padded rows = per-shard capacity x local shards;
            # every process agrees on the capacity (synced in the
            # layout builder), so multi-host blocks stay equal
            self.n_pad = self._shard_layout.n_pad
        else:
            n_for_pad = self._n_pad_base if self._mh else n
            self.n_pad = ((n_for_pad + row_unit - 1) // row_unit) \
                * row_unit

        # ordered-partition growth (pallas learner, serial or single-host
        # data-parallel): block-list sweeps are always on (bit-identical
        # to full sweeps for a fixed row order — empty blocks contribute
        # exact zeros); the row re-sort that makes them leaf-proportional
        # additionally needs the fused path and a permutable objective.
        # Bagging composes: the in/out-of-bag draw stays pinned to FILE
        # order (mt19937 parity) and the mask permutes on device per
        # re-bagging (_bag_mask_dev_fused).
        self.hist_ranged = (config.hist_ordered != "off"
                            and impl == "pallas"
                            and (self.grower is None
                                 or self._fused_sharded))
        self.reorder_every = max(int(config.hist_reorder_every), 1)
        self._row_order = None        # [n_pad] i32 device; None = identity
        self._inv_order = None        # cached device inverse of the above
        self._gstate_override = None
        self._trees_since_reorder = self._unsorted_interval()
        # the packed int rows (device) of the trees the fused step grew
        # last, the latest first: the re-sort's and the bag arrangement's
        # key (_prev_trees)
        self._prev_ints = ()

        # out-of-core ingest (ingest/ShardedDataset): feed the device
        # one shard window at a time — the full [F, N] matrix never
        # exists on the host.  The query-granular layout still needs a
        # host scatter (place()), so it takes the materializing
        # fallback (ShardedDataset.bins logs it); so does the FEATURE-
        # sharded learner, whose grower splits F (every rank holds all
        # rows by that learner's premise — out-of-core row feeding
        # cannot help it).
        streamed = (getattr(train_data, "is_shard_backed", False)
                    and self._shard_layout is None
                    and (self.grower is None or self.rows_sharded))
        bins = None if streamed else train_data.bins
        # the bin matrix, the scores and what pads them on their way to
        # the device; the span ends where the host's part ends (the
        # transfers are asynchronous and nothing waits for them here)
        with spans.startup(spans.STARTUP_UPLOAD,
                           shards=self._shards) as uploaded:
            self.scores = self._init_scores(train_data, n)
            if self._shard_layout is not None:
                # query-granular layout: file rows scatter into per-shard
                # blocks; gap rows (like trailing pad rows) stay permanently
                # out-of-bag and their scores are never read
                bins = self._shard_layout.place(bins)
                self.scores = jnp.asarray(
                    self._shard_layout.place(np.asarray(self.scores)))
            elif self.n_pad != n:
                # a row-sharded grower pads block by block (shard_bins)
                if bins is not None and not self.rows_sharded:
                    bins = np.pad(bins, ((0, 0), (0, self.n_pad - n)))
                self.scores = jnp.pad(self.scores,
                                      ((0, 0), (0, self.n_pad - n)))
            if self.grower is not None:
                if streamed:
                    self.bins_dev = self._put_bins_sharded_streamed(train_data)
                elif self.rows_sharded:
                    self.bins_dev = self.grower.shard_bins(bins, self.n_pad)
                else:
                    self.bins_dev = self.grower.shard_bins(bins)
                if self.rows_sharded and not self._mh:
                    # single-host: shard scores so the leaf_id gather-add
                    # stays on-device
                    self.scores = jax.device_put(
                        self.scores, self.grower.row_sharding_2d())
                elif self._mh_fused:
                    # multi-host fused: scores become a GLOBAL row-sharded
                    # array once — every later iteration touches them only
                    # inside the fused dispatch (process p's file rows live
                    # at global positions [p*n_pad, (p+1)*n_pad))
                    self.scores = self.grower.shard_rows(
                        np.asarray(self.scores), self.n_pad)
            else:
                self.bins_dev = (self._put_bins_streamed(train_data)
                                 if streamed else jnp.asarray(bins))
            uploaded["bytes"] = (int(self.bins_dev.nbytes)
                                 + int(self.scores.nbytes))
        if objective is not None and self.n_pad != n:
            objective.pad_to(self.n_pad)

        # bagging state (gbdt.cpp:70-79); padded rows stay False forever
        self.bagging_enabled = (config.bagging_fraction < 1.0
                                and config.bagging_freq > 0)
        # 1-leaf-stump stop detection is batched: fetching num_leaves every
        # iteration costs a device->host roundtrip (tens of ms on remote-
        # attached TPUs) that would serialize the async dispatch pipeline.
        # Deferral is only sound when a stump implies every later tree is
        # an identical zero-valued stump (so late truncation at the next
        # flush reproduces the reference's stop point, gbdt.cpp:186, with
        # no numerical difference): single-class, no bagging, no
        # feature_fraction — under those, per-tree masks change and a real
        # tree can follow a stump, so flush every iteration.  DART sets 1
        # too (dropping needs host trees each iteration), and
        # train_one_iter forces a flush when gradients come from a custom
        # objective (their evolution is outside the soundness argument).
        # Since round 3, deferral is sound for bagged/feature-fraction
        # runs too: the fused step carries a DEVICE stopped flag — after
        # the first stump every later step no-ops its score updates, so
        # a late flush truncates at the exact reference stop point with
        # scores untouched past it (the earlier host-sync-per-iteration
        # requirement is gone).  Multiclass still flushes per iteration
        # (general path, per-class trees).
        # The general (non-fused) path has no device flag and still needs
        # the old soundness condition (no bagging / feature_fraction);
        # DART re-forces 1 in its own __init__.
        # Since round 4, the multiclass FUSED path is deferrable too: its
        # class-wise scan carries the same device stopped flag, so score
        # updates stop at the exact stump (including later classes of the
        # stump's iteration) and a late flush truncates correctly.
        deferrable = ((self.num_class == 1
                       and (self._can_fuse()
                            or (not self.bagging_enabled
                                and config.feature_fraction >= 1.0)))
                      or self._can_fuse_multi())
        self._flush_every = 16 if deferrable else 1
        # multi-host fused: every input of the global fused dispatch must
        # be a global array, including the scalar stopped flag.  Single-
        # host sharded: the first dispatch's inputs are placed as every
        # later dispatch's are (the step's own outputs), else the re-sort
        # step compiles a second time at its second call (the flag here,
        # the bag mask in _bag_mask_dev_fused, the gradient state in
        # _gstate_for_fused)
        self._dev_stopped = (self.grower.replicate(np.asarray(False))
                             if self._fused_sharded else jnp.asarray(False))
        self.bag_rng = Mt19937Random(config.bagging_seed)
        # bag compaction (config.bag_compact): in-bag rows arranged into
        # a contiguous STATIC window at every re-bagging so the fused
        # step's histogram/grow work scales with bagging_fraction.  The
        # window size is computed lazily on first use (_bag_compact_rows
        # — DART's fusibility check needs its own __init__ to have run);
        # None = not computed yet, 0 = compaction off.
        self._bag_window = None
        self._bag_arranged = False     # device state currently in-bag-first
        self._bag_overflowed = False   # sharded margin overflow -> masked
        self.bag_masks = []
        for _ in range(self.num_class):
            m = np.zeros(self.n_pad, dtype=bool)
            m[:n] = True
            self.bag_masks.append(m)
        # sharded/device bag masks are cached; _bagging invalidates
        self._bag_dev = [None] * self.num_class
        self._bag_dev_packed = [None] * self.num_class
        self._bag_stacked = None    # [K, n_pad] stack (multiclass fused)
        # what lgbm.flush says of the sampling: the newest bag's rows and
        # the draws since the last flush
        self._bag_in_bag = 0
        self._bag_draws = 0
        # per-class feature-fraction RNG, all seeded feature_fraction_seed
        # (one TreeLearner per class in the reference, gbdt.cpp:38-45)
        self.feat_rngs = [Mt19937Random(config.feature_fraction_seed)
                          for _ in range(self.num_class)]

    # ------------------------------------------------------------------
    def _init_scores(self, data: Dataset, n: int) -> jax.Array:
        k = self.num_class
        if data.metadata.init_score is not None:
            init = np.asarray(data.metadata.init_score, dtype=np.float32)
            if init.size == n * k:
                return jnp.asarray(init.reshape(k, n))
            log.warning("init score size mismatch, ignoring")
        return jnp.zeros((k, n), dtype=jnp.float32)

    def _put_bins_streamed(self, ds) -> jax.Array:
        """Device bins assembled one shard window at a time (out-of-core
        ingest): each [F, k] window device_puts independently and the
        concatenation happens ON DEVICE, so peak host memory is
        2 + ingest_prefetch windows (queued + producer-staged +
        consumer-held) — the full matrix exists only in device memory,
        where training needs it anyway.

        Double-buffered since round 16 (config.ingest_prefetch): the
        windows arrive through a bounded background prefetch thread
        (ingest/shards.prefetch_windows), so the NEXT shard pages in
        from disk while the previous window's async device_put transfer
        is still in flight — the load phase overlaps host IO with
        host->device copy instead of alternating, and training then
        runs on the same device-resident state as the in-memory path
        (shard-fed steady == in-memory steady).  The prefetcher changes
        WHEN windows are staged, never their order or bytes: shard-fed
        models are byte-identical with overlap on or off (tested)."""
        from ..ingest.shards import prefetch_windows
        parts = [jax.device_put(w)
                 for w in prefetch_windows(ds.iter_bin_windows(),
                                           self.config.ingest_prefetch)]
        pad = self.n_pad - ds.num_data
        if pad > 0:
            parts.append(jnp.zeros((ds.num_features, pad),
                                   dtype=ds.bin_dtype))
        if len(parts) == 1:
            return parts[0]
        return jnp.concatenate(parts, axis=1)

    def _put_bins_sharded_streamed(self, ds) -> jax.Array:
        """Shard-window feeding for the data/voting-parallel growers.
        Multi-host: the global array assembles from this process's
        LOCAL block — the rank's manifest slice, 1/R of the data —
        which is the out-of-core scaling contract (each host pays for
        its slice, never the file).  Single-host: each mesh device's
        row block assembles on the host (peak: ONE block + one
        window) and device_puts straight to ITS device — no device
        ever stages the full matrix, so per-chip HBM holds 1/S of the
        data exactly like the host path's sharded placement.  The
        single-host leg stages its shard reads through the bounded
        background prefetch thread (config.ingest_prefetch) so disk IO
        overlaps the per-device transfers; the mh leg assembles its
        local block synchronously (its consumer does no per-window
        work, so prefetch would only add staged-window footprint —
        see ShardedDataset.local_bins_matrix)."""
        from ..ingest.shards import prefetch_windows
        if self._mh:
            local = ds.local_bins_matrix()
            if local.shape[1] < self.n_pad:
                local = np.pad(
                    local, ((0, 0), (0, self.n_pad - local.shape[1])))
            return self.grower.shard_bins(local)
        sharding = self.grower.bins_sharding()
        devs = list(self.grower.mesh.devices.flat)
        block = self.n_pad // len(devs)   # n_pad is row_unit*S-aligned
        f = ds.num_features
        cur = np.zeros((f, block), dtype=ds.bin_dtype)
        pieces = []
        fill = 0
        for w in prefetch_windows(ds.iter_bin_windows(),
                                  self.config.ingest_prefetch):
            o = 0
            k = w.shape[1]
            while o < k:
                take = min(block - fill, k - o)
                cur[:, fill:fill + take] = w[:, o:o + take]
                fill += take
                o += take
                if fill == block:
                    pieces.append(jax.device_put(cur,
                                                 devs[len(pieces)]))
                    cur = np.zeros((f, block), dtype=ds.bin_dtype)
                    fill = 0
        while len(pieces) < len(devs):   # trailing pad blocks (zeros)
            pieces.append(jax.device_put(cur, devs[len(pieces)]))
            cur = np.zeros((f, block), dtype=ds.bin_dtype)
        return jax.make_array_from_single_device_arrays(
            (f, self.n_pad), sharding, pieces)

    def add_valid_data(self, data: Dataset, metrics: Sequence[Metric]) -> None:
        if self.iter > 0:
            log.fatal("Cannot add validation data after training started")
        self.valid_data.append(data)
        self.valid_metrics.append(list(metrics))
        # multi-host fused: valid arrays enter the global fused dispatch
        # as REPLICATED globals (every process loaded the same valid
        # file, matching the reference's per-machine valid copy)
        put = (self.grower.replicate if self._mh_fused else jnp.asarray)
        self.valid_bins_dev.append(put(data.bins))
        k = self.num_class
        vn = data.num_data
        if (data.metadata.init_score is not None
                and np.asarray(data.metadata.init_score).size == vn * k):
            init = np.asarray(data.metadata.init_score, dtype=np.float32)
            self.valid_scores.append(put(init.reshape(k, vn)))
        else:
            self.valid_scores.append(put(np.zeros((k, vn),
                                                  dtype=np.float32)))
        if self.early_stopping_round > 0:
            self.best_iter.append([0] * len(metrics))
            self.best_score.append([-np.inf] * len(metrics))

    # ------------------------------------------------------------------
    def _bagging(self, it: int, cls: int) -> None:
        """GBDT::Bagging (gbdt.cpp:109-160): row- or query-granular
        reservoir selection, drawing from the shared bagging stream.

        What a redraw costs the host: one pass of the native walk over
        2 x rows words of the stream (utils/mt19937.py; 0.69 s at 68.3M
        rows on the chip's host where the numpy twist took 19.9 s, PERF.md
        section 6, PR 33) into ONE new bool[n_pad], the mask itself; no
        array of draws, no second copy."""
        cfg = self.config
        if not self.bagging_enabled or it % cfg.bagging_freq != 0:
            return
        md = self.train_data.metadata
        n = self.num_data
        with TraceAnnotation(spans.BAG_DRAW, iter=it, rows=n) as draw_span:
            padded = np.zeros(self.n_pad, dtype=bool)
            if md.query_boundaries is None:
                bag_cnt = int(cfg.bagging_fraction * n)
                self.bag_rng.split_mask(n, bag_cnt, out=padded[:n])
            else:
                qb = md.query_boundaries
                nq = len(qb) - 1
                bag_query_cnt = int(nq * cfg.bagging_fraction)
                qmask = self.bag_rng.split_mask(nq, bag_query_cnt)
                for q in np.nonzero(qmask)[0]:
                    padded[qb[q]:qb[q + 1]] = True
            self._bag_in_bag = int(np.count_nonzero(padded))
            self._bag_draws += 1
            draw_span.set_metadata(in_bag=self._bag_in_bag)
        self.bag_masks[cls] = padded
        self._bag_dev[cls] = None
        self._bag_dev_packed[cls] = None
        self._bag_stacked = None
        # a redraw invalidates the in-bag-first arrangement; the next
        # fused dispatch re-arranges (_ensure_bag_arranged)
        self._bag_arranged = False
        log.debug("Re-bagging, using %d data to train" % self._bag_in_bag)

    def bag_mask(self, cls: int = 0) -> np.ndarray:
        """The current bag of class `cls` in FILE order, [num_data] bool:
        a copy (all True until a first draw)."""
        return self.bag_masks[cls][:self.num_data].copy()

    def _feature_mask(self, cls: int) -> np.ndarray:
        f = self.train_data.num_features
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return np.ones(f, dtype=bool)
        used_cnt = int(f * frac)
        idx = self.feat_rngs[cls].sample(f, used_cnt)
        mask = np.zeros(f, dtype=bool)
        mask[idx] = True
        return mask

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients=None, hessians=None,
                       is_eval: bool = True) -> bool:
        """One boosting iteration (gbdt.cpp:169-205). Returns True when
        training must stop.  A segment of one in the profiler's trace;
        subclasses override _one_iter."""
        with TraceAnnotation(spans.SEGMENT, iter=self.iter, k=1):
            return self._one_iter(gradients, hessians, is_eval)

    def _one_iter(self, gradients, hessians, is_eval: bool) -> bool:
        cfg = self.config
        if gradients is None and self._can_fuse():
            # fully-fused iteration: gradients -> grow -> score updates ->
            # tree packing in ONE dispatch with donated score buffers
            with TraceAnnotation(spans.HOST_INPUTS):
                self._ensure_layout()
                self._bagging(self.iter, 0)
                self._ensure_bag_arranged()
                fmask = self._feature_mask(0)
                fmask_dev = (self.grower.replicate(fmask) if self._mh_fused
                             else jnp.asarray(fmask))
                bag_dev = self._bag_mask_dev_fused(0)
            self._models.extend(self._run_fused(bag_dev, fmask_dev))
        elif gradients is None and self._can_fuse_multi():
            # multiclass fused iteration: all K per-iteration trees in
            # one dispatch (class-wise scan, _make_fused_step_multi)
            self._models.extend(self._run_fused_multi())
        else:
            # leaving the fused path (custom gradients / objective swap):
            # gradients arrive in FILE order, so per-row state must be
            # restored to file order first or rows and gradients misalign
            self._restore_row_order()
            if gradients is None or hessians is None:
                grad, hess = self.objective.get_gradients(
                    self._score_for_gradients())
                if grad.ndim == 1:
                    grad = grad[None, :]
                    hess = hess[None, :]
            else:
                grad = jnp.asarray(gradients, dtype=jnp.float32).reshape(
                    self.num_class, self.num_data)
                hess = jnp.asarray(hessians, dtype=jnp.float32).reshape(
                    self.num_class, self.num_data)
                if self.n_pad != self.num_data:
                    pad = ((0, 0), (0, self.n_pad - self.num_data))
                    grad = jnp.pad(grad, pad)
                    hess = jnp.pad(hess, pad)
            for cls in range(self.num_class):
                with TraceAnnotation(spans.HOST_INPUTS):
                    self._bagging(self.iter, cls)
                    fmask = self._feature_mask(cls)
                    bag_dev = self._bag_mask_dev(cls)
                self._models.append(self._train_tree(
                    grad[cls], hess[cls], bag_dev, fmask, cls))
        self.iter += 1
        self.num_used_model = len(self._models) // self.num_class
        custom_grads = gradients is not None
        if (custom_grads or self.iter % self._flush_every == 0) \
                and not is_eval:
            # multi-host: the stump stop must be OR-synced on the
            # non-eval flush paths too — a lone rank stopping would
            # leave the others blocked in their next collective.  The
            # eval path defers to eval_and_check_early_stopping, which
            # flushes (and syncs) first thing, so the collective runs
            # exactly once per iteration.
            if self._sync_stop(self._flush_pending()):
                log.info("Stopped training because there are no more leafs "
                         "that meet the split requirements.")
                return True
        if is_eval:
            return self.eval_and_check_early_stopping()
        return False

    def _grow_kw(self) -> dict:
        """The grower configuration shared by every training path (the
        three fused step builders and the general _train_tree); one
        definition so they cannot drift."""
        cfg = self.config
        return dict(max_leaves=max(cfg.num_leaves, 2),
                    max_bin=self.max_bin, params=self.params,
                    max_depth=cfg.max_depth, hist_impl=self.hist_impl,
                    hist_slots=self.hist_slots, ranged=self.hist_ranged)

    def _bag_mask_dev(self, cls: int):
        """Device/sharded bag mask, uploaded only when bagging changed it."""
        if self._bag_dev[cls] is None:
            mask = self.bag_masks[cls]
            if self.grower is not None:
                self._bag_dev[cls] = self.grower.shard_rows(mask, self.n_pad)
            else:
                self._bag_dev[cls] = jnp.asarray(mask)
        return self._bag_dev[cls]

    def _bag_mask_dev_packed(self, cls: int):
        """Bit-packed bag mask for the fused step (8x less transfer per
        re-bagging; the step unpacks on device).  The ordered-partition
        re-sort replaces this cache with an already-permuted bool mask —
        _unpack_bag passes bool through."""
        if self._bag_dev_packed[cls] is None:
            self._bag_dev_packed[cls] = jnp.asarray(
                np.packbits(self.bag_masks[cls]))
        return self._bag_dev_packed[cls]

    @contract.rank_uniform
    def _can_fuse(self) -> bool:
        """The fused single-dispatch iteration covers the single-class
        path with a jax-traceable objective (regression/binary) on the
        serial learner OR single-host tree_learner=data (shard_map
        variant, _make_fused_step_sharded); DART (per-iteration score
        surgery + varying shrinkage), custom gradients, multiclass,
        multi-host and voting/feature growers take the general path.
        The sharded variant additionally needs a row_shardable objective
        — elementwise grad_state shards along the data axis, and
        lambdarank's query-block state shards query-granularly through
        its own RowShardLayout (shard_layout/build_sharded_state), so
        rank runs the fused sharded step too; rank_impl=native keeps
        the general path (host gradients)."""
        return (type(self) is GBDT and self.num_class == 1
                and (self.grower is None
                     or (self._fused_sharded
                         and getattr(self.objective, "row_shardable",
                                     False)))
                and getattr(self.objective, "jax_traceable", False)
                and self.objective.fused_key() is not None)

    @contract.rank_uniform
    def _can_fuse_multi(self) -> bool:
        """The multiclass fused iteration (_make_fused_step_multi):
        serial learner OR tree_learner=data (the shard_map variant,
        _make_fused_step_multi_sharded — VERDICT r4 #3, single- AND
        multi-host since round 5), K > 1, traceable row-shardable
        objective.  DART overrides via type check (its per-iteration
        drop surgery needs host trees)."""
        return (type(self) is GBDT and self.num_class > 1
                and (self.grower is None
                     or (self._fused_sharded
                         and getattr(self.objective, "row_shardable",
                                     False)))
                and getattr(self.objective, "jax_traceable", False)
                and self.objective.fused_key() is not None)

    def _bag_masks_stacked_dev(self):
        """[K, n_pad] bool device stack of the per-class bag masks for
        the multiclass fused step; rebuilt only when re-bagging
        invalidated it (_bagging clears the cache).  Host masks stay in
        FILE order (mt19937 parity); under an active shared row order
        the rebuilt stack permutes once on device — the reorder step
        keeps the cached stack permuted thereafter."""
        if self._bag_stacked is None:
            stack = np.stack(self.bag_masks)
            # multi-host: local file-order draws assemble into the
            # global [K, N] row-sharded mask
            m = (self.grower.shard_rows(stack, self.n_pad)
                 if self._mh_fused else jnp.asarray(stack))
            if self._row_order is not None:
                if self.grower is not None:
                    # sharded fused multiclass: shard-local permute, not
                    # a cross-shard global gather
                    m = self.grower.permute_rows(m, self._row_order)
                else:
                    m = jnp.take(m, self._row_order, axis=1)
            self._bag_stacked = m
        return self._bag_stacked

    def _run_fused_multi(self, k_iters: int = 1):
        cfg = self.config
        lr = self.shrinkage_rate
        # per-iteration host draws in the exact sequential order: class-
        # wise bagging (a no-op past the segment's first iteration — the
        # scheduler ends segments at re-bag boundaries), then the K
        # per-class feature masks
        fmasks_list = []
        with TraceAnnotation(spans.HOST_INPUTS):
            for j in range(k_iters):
                for cls in range(self.num_class):
                    self._bagging(self.iter + j, cls)
                if j == 0:
                    self._ensure_bag_arranged()
                fmasks_list.append(
                    np.stack([self._feature_mask(c)
                              for c in range(self.num_class)]))
            fmasks = (fmasks_list[0] if k_iters == 1
                      else np.stack(fmasks_list))
        # shared-joint-order ordered-partition growth (round 4): same
        # gate and cadence as the single-class reorder — re-sort after
        # the first iteration, then every reorder_every (hist_ranged
        # already requires serial or the fused sharded learner)
        reorder = self._reorder_now_multi()
        compact = self._bag_compact_rows() if self._bag_arranged else 0
        gstate = self._gstate_for_fused()
        key = ("multi", self.objective.fused_key(), lr, self.dtype,
               self.hist_impl, self.max_bin, max(cfg.num_leaves, 2),
               cfg.max_depth, self.params, len(self.valid_bins_dev),
               self.hist_slots, self.hist_ranged,
               reorder, compact, k_iters,
               (cfg.hist_agg, self.grower.num_shards,
                id(self.grower.mesh)) if self.grower is not None else None)

        row_state = self.objective.make_row_state_fn()

        def make():
            grow_kw = self._grow_kw()
            if self.grower is not None:
                # single-host tree_learner=data (VERDICT r4 #3): the
                # class-wise scan under shard_map, same protocol wiring
                # as the single-class sharded step
                from ..parallel.mesh import DATA_AXIS
                grow_kw.update(psum_axis=DATA_AXIS,
                               hist_agg=cfg.hist_agg,
                               num_shards=self.grower.num_shards,
                               voting_top_k=0)
                return _make_fused_step_multi_sharded(
                    self.objective.make_grad_fn(), grow_kw, lr,
                    self.dtype, self.grower.mesh,
                    len(self.valid_bins_dev),
                    self._fused_gspecs(gstate), reorder, row_state,
                    compact, k_iters)
            return _make_fused_step_multi(self.objective.make_grad_fn(),
                                          grow_kw, lr, self.dtype,
                                          reorder, row_state, compact,
                                          k_iters)

        fn = _get_fused_step(key, make)
        with TraceAnnotation(spans.HOST_INPUTS):
            fmasks_dev = (self.grower.replicate(fmasks) if self._mh_fused
                          else jnp.asarray(fmasks))
            common = (self.scores, list(self.valid_scores),
                      self._bag_masks_stacked_dev(), fmasks_dev,
                      self.bins_dev, tuple(self.valid_bins_dev), gstate,
                      self._dev_stopped)
            stats = {"classes": self.num_class}
            if reorder:
                common += (self._row_order if self._row_order is not None
                           else self._identity_order_dev(),)
                stats.update(_resort_counts(
                    [common[4], common[0], common[2], common[8]], gstate,
                    row_state))
        with _enqueue("multi", k_iters, self._shards, **stats):
            out = fn(*common)
        if reorder:
            (scores, valid, ints_k, floats_k, self._dev_stopped,
             self.bins_dev, self._bag_stacked, self._gstate_override,
             self._row_order) = out
            self._inv_order = None
            self._trees_since_reorder = 0
        else:
            scores, valid, ints_k, floats_k, self._dev_stopped = out
            self._trees_since_reorder += k_iters
        self.scores = scores
        self.valid_scores = list(valid)
        # device row slices stay unmaterialized: _flush_pending stacks
        # and pulls every pending tree in ONE transfer
        if k_iters == 1:
            return [_PendingTree(ints_k[c], floats_k[c], lr, gated=True)
                    for c in range(self.num_class)]
        return [_PendingTree(ints_k[j, c], floats_k[j, c], lr, gated=True)
                for j in range(k_iters) for c in range(self.num_class)]

    def _gstate_for_fused(self):
        """Gradient state for the fused dispatch: the cached permuted/
        global override when present, else the objective's own arrays —
        assembled ONCE into global row-sharded arrays under multi-host
        (the reorder steps keep the cached state permuted).  Under the
        query-granular layout the objective builds its shard-major state
        instead (lambdarank: per-shard query blocks with shard-local doc
        indices), placed once via put_spec."""
        gstate = self._gstate_override
        if gstate is None:
            if self._layout_active:
                host, specs = self._build_sharded_gstate_host()
                self._gstate_specs = specs
                gstate = tuple(self.grower.put_spec(a, sp)
                               for a, sp in zip(host, specs))
                self._gstate_override = gstate
                return gstate
            gstate = self.objective.grad_state()
            if self._mh_fused:
                gstate = jax.tree_util.tree_map(
                    lambda a: self.grower.shard_rows(np.asarray(a),
                                                     self.n_pad), gstate)
                self._gstate_override = gstate
            elif self._fused_sharded:
                # chip to chip, once: the objective's arrays sit on the
                # first device
                from jax.sharding import NamedSharding
                gstate = jax.tree_util.tree_map(
                    lambda a, spec: jax.device_put(
                        a, NamedSharding(self.grower.mesh, spec)),
                    gstate, self._fused_gspecs(gstate))
                self._gstate_override = gstate
        return gstate

    def _build_sharded_gstate_host(self):
        """(host_leaves, specs) of the objective's query-sharded state
        (multi-host syncs the block shapes so every process's put
        agrees)."""
        sync = None
        if self._mh_fused:
            from ..parallel.dist import sync_max_ints
            sync = sync_max_ints
        return self.objective.build_sharded_state(self._shard_layout,
                                                  sync=sync)

    def _identity_order_dev(self):
        """Initial ordered-partition row order: global POSITIONS
        (process p's file rows start at p * n_pad under the equal-block
        multi-host assembly)."""
        if self._mh_fused:
            base = jax.process_index() * self.n_pad
            return self.grower.shard_rows(
                np.arange(base, base + self.n_pad, dtype=np.int32),
                self.n_pad)
        if self._fused_sharded:     # each chip makes its own block
            return jnp.arange(self.n_pad, dtype=jnp.int32,
                              device=self.grower.row_sharding())
        return jnp.arange(self.n_pad, dtype=jnp.int32)

    def _reorder_enabled(self) -> bool:
        # bagging composes with the ordered partition since round 3:
        # masks draw on the host in FILE order (mt19937 parity) and are
        # permuted once per re-bagging on device (_bag_mask_dev_fused)
        return (self.hist_ranged
                and getattr(self.objective, "row_permutable", False)
                and self._can_fuse())

    def _reorder_due(self) -> bool:
        """Does the NEXT iteration hit the re-sort cadence?  (First tree
        re-sorts — clustering pays from tree 2 on — then every
        reorder_every trees: rows in file order count as a whole
        interval behind, _unsorted_interval.)"""
        return self._trees_since_reorder >= self.reorder_every - 1

    def _unsorted_interval(self) -> int:
        """_trees_since_reorder of rows no tree has sorted: the next
        fused tree re-sorts.  (Until PR 33 `_row_order is None` stood for
        this; but a bag's arrangement makes a row order BEFORE the first
        tree, so a job that bags never sorted at its first tree, grew
        its first interval on unclustered rows and compiled a K=1 scan
        for that one tree.)"""
        return self.reorder_every - 1

    def _reorder_now(self) -> bool:
        return self._reorder_enabled() and self._reorder_due()

    def _ordered_on_multi(self) -> bool:
        """The multiclass ordered-partition gate (shared by the segment
        scheduler and the dispatch so they can never disagree on which
        body variant a segment runs)."""
        return (self.hist_ranged
                and getattr(self.objective, "row_permutable", False))

    def _reorder_now_multi(self) -> bool:
        return self._ordered_on_multi() and self._reorder_due()

    # -- iteration batching (config.iter_batch): segment scheduling ----
    def _iter_batch_k(self) -> int:
        """The configured dispatch batch K (1 = per-iteration oracle)."""
        v = self.config.iter_batch
        if v == "auto":
            return self._auto_iter_batch()
        return max(int(v), 1)

    _ITER_BATCH_AUTO = 8

    def _auto_iter_batch(self) -> int:
        """auto K: the default batch on ACCELERATORS, shrunk to the
        largest divisor of metric_freq when metric output is live so
        segments tile the metric grid with ONE executable instead of an
        alternating pair.  On the CPU backend auto resolves to 1: local
        dispatch costs microseconds, so the one host dispatch and sync
        per iteration that batching removes does not repay the K-scan's
        extra XLA CPU compile time (explicit iter_batch=N still forces
        batching anywhere)."""
        if jax.devices()[0].platform == "cpu":
            return 1
        return self._auto_iter_batch_accel()

    def _auto_iter_batch_accel(self) -> int:
        k = self._ITER_BATCH_AUTO
        if self._metrics_active():
            mf = max(int(self.config.metric_freq), 1)
            k = min(k, mf)
            while mf % k:
                k -= 1
        return k

    def _metrics_active(self) -> bool:
        return (bool(self.training_metrics)
                or any(len(ms) > 0 for ms in self.valid_metrics))

    def _segment_fusible(self) -> bool:
        """Paths the batched dispatch covers (the general per-tree path
        keeps K=1: its per-iteration grad round-trip is the thing the
        fused steps already removed)."""
        return self._can_fuse() or self._can_fuse_multi()

    @contract.rank_uniform
    def _plan_segment(self, max_iters: int, is_eval: bool) -> int:
        """K for the next dispatch: min(iter_batch, metric boundary,
        early-stop check, re-bagging epoch boundary, re-sort cadence,
        remaining iterations) — every host-observable boundary ends a
        segment, so batched training is bit-parity with the K=1 oracle
        including the exact metric lines, early-stop iteration, bagging
        epochs and checkpoints."""
        k = min(self._iter_batch_k(), max_iters)
        if k <= 1 or self._stopped or not self._segment_fusible():
            return 1
        if is_eval:
            if self.early_stopping_round > 0:
                # the reference checks early stopping every iteration;
                # batching would skip checks, so the oracle cadence wins
                return 1
            if self._metrics_active():
                mf = max(int(self.config.metric_freq), 1)
                k = min(k, mf - self.iter % mf)
        if self.bagging_enabled:
            freq = max(int(self.config.bagging_freq), 1)
            # iteration `it` re-bags when it % freq == 0; the segment
            # may start ON a boundary but not cross the next one
            k = min(k, freq - self.iter % freq)
        ordered_on = (self._reorder_enabled() if self.num_class == 1
                      else self._ordered_on_multi())
        if ordered_on:
            if self.reorder_every > 1:
                if self._reorder_due():
                    return 1     # the re-sort dispatch runs alone
                k = min(k, self.reorder_every - 1
                        - self._trees_since_reorder)
            # reorder_every == 1: every iteration re-sorts — the segment
            # scans the reorder body uniformly, no cap needed
        # (DART needs no extra cap: its tree rows grow to fit any k
        # before the dispatch, and a tree past its leaf bank is replayed)
        return max(k, 1)

    @contract.rank_uniform
    def train_segment(self, max_iters: int,
                      is_eval: bool = True) -> "Tuple[bool, int]":
        """Train up to max_iters boosting iterations, batching
        K = _plan_segment of them into ONE device dispatch; host work
        (metric lines, early stopping, flushes, re-bagging draws) runs
        only at segment boundaries, exactly where the K=1 loop would
        have run it.  Returns (stop, iterations_done)."""
        with TraceAnnotation(spans.HOST_INPUTS):
            k = self._plan_segment(max_iters, is_eval)
        if k <= 1:
            return self.train_one_iter(None, None, is_eval), 1
        with TraceAnnotation(spans.SEGMENT, iter=self.iter, k=k):
            it0 = self.iter
            self._train_segment_fused(k)
            self.iter += k
            self.num_used_model = len(self._models) // self.num_class
            if is_eval:
                return self.eval_and_check_early_stopping(), k
            if it0 // self._flush_every != self.iter // self._flush_every:
                # the segment crossed a deferred-flush boundary: same
                # amortized device->host pull cadence as the K=1 loop
                if self._sync_stop(self._flush_pending()):
                    log.info("Stopped training because there are no more "
                             "leafs that meet the split requirements.")
                    return True, k
            return False, k

    def _train_segment_fused(self, k: int) -> None:
        """Dispatch one K-iteration segment and append the pending trees
        (DART overrides with its banked variant)."""
        if self._can_fuse():
            with TraceAnnotation(spans.HOST_INPUTS):
                self._ensure_layout()
                self._bagging(self.iter, 0)
                self._ensure_bag_arranged()
                fmasks = np.stack([self._feature_mask(0)
                                   for _ in range(k)])
                fmasks_dev = (self.grower.replicate(fmasks)
                              if self._mh_fused else jnp.asarray(fmasks))
                bag_dev = self._bag_mask_dev_fused(0)
            self._models.extend(self._run_fused(bag_dev, fmasks_dev, k))
        else:
            self._models.extend(self._run_fused_multi(k))

    def _bag_mask_dev_fused(self, cls: int):
        """Fused-path bag mask: bit-packed file-order upload normally;
        under an active row order, the cached ORDERED bool mask —
        rebuilt (unpack + one device take) only when re-bagging
        invalidated it.  The reorder step keeps this cache permuted.
        The SHARDED fused step always takes the bool mask: a packed byte
        row only splits on shard boundaries when N_local % 8 == 0, which
        the xla hist impl does not guarantee."""
        if self._fused_sharded:
            if self._bag_dev_packed[cls] is None:
                # multi-host: the local file-order draw (mt19937 parity
                # with the reference's per-machine bagging) assembles
                # into the global row-sharded mask; the order permute is
                # shard-local by construction (ShardedGrower.permute_rows)
                m_host = self.bag_masks[cls]
                if self._layout_active:
                    # query-granular layout: file-order draw scatters
                    # into the per-shard blocks; gap rows stay False
                    m_host = self._shard_layout.place(
                        m_host[:self.num_data], fill=False)
                m = self.grower.shard_rows(m_host, self.n_pad)
                if self._row_order is not None:
                    m = self.grower.permute_rows(m, self._row_order)
                self._bag_dev_packed[cls] = m
            return self._bag_dev_packed[cls]
        if self._row_order is None:
            return self._bag_mask_dev_packed(cls)
        if self._bag_dev_packed[cls] is None:
            self._bag_dev_packed[cls] = _permute_packed_bag(
                self._bag_mask_dev_packed(cls), self._row_order)
        return self._bag_dev_packed[cls]

    # -- bag compaction (config.bag_compact) ---------------------------
    def _compact_fusible(self) -> bool:
        """Does this booster run a fused path compaction can attach to?
        (DART overrides with its banked-path check.)"""
        return self._can_fuse() or self._can_fuse_multi()

    def _compute_bag_window(self) -> int:
        """Static compacted sweep window in rows (0 = compaction off):
        ceil_pad of a deterministic upper bound on any draw's in-bag
        count, so shapes are stable and one executable serves every
        re-bagging epoch.  Serial bounds are exact (row bagging draws
        exactly int(fraction*n) rows; query bagging is bounded by the
        largest that-many queries).  Sharded learners get a per-shard
        window: expected count plus a generous margin, with a host-side
        overflow check per re-bagging (_ensure_bag_arranged) that falls
        back to the masked path if a freak draw exceeds it."""
        cfg = self.config
        if (not self.bagging_enabled or cfg.bag_compact == "off"
                or not self._compact_fusible()
                or not getattr(self.objective, "row_permutable", False)):
            return 0
        if cfg.bag_compact == "auto":
            # auto keeps the f64 parity configuration on the masked
            # full-sweep oracle and skips fractions too close to 1
            if (cfg.bagging_fraction > 0.8
                    or self.dtype != jnp.float32):
                return 0
        unit = self._row_unit_base
        bound = self.objective.bag_rows_bound(cfg.bagging_fraction)
        if self.num_class > 1:
            # per-class draws differ: the window must hold their UNION
            bound = min(self.num_data, self.num_class * bound)
        if self._fused_sharded:
            import math
            cap = self.n_pad // self.grower.local_shard_count()
            frac = min(bound / max(self.num_data, 1), 1.0)
            # margin: 4 sigma of the per-shard hypergeometric count (the
            # binomial sigma bounds it), floored at cap/8 so query-
            # granular draws' row clumping is covered too
            sigma = math.sqrt(cap * frac * (1.0 - frac))
            w = int(cap * frac) + max(unit, cap // 8, int(4 * sigma) + 1)
            w = min(-(-w // unit) * unit, cap)
            return w if w < cap else 0
        if self.grower is not None:
            return 0   # feature/voting growers keep the masked path
        w = -(-max(bound, 1) // unit) * unit
        # whole groups of 8 row blocks, where that still leaves a tail: the
        # steps of a window of 6,674 blocks took 30-36 s each to compile
        # for the chip, of 6,672 or 6,680 blocks 9-10 s (PERF.md section 6,
        # PR 33: every count that is no multiple of 8 tried below 8,192
        # blocks was slow), for 0.1% more rows swept
        w8 = -(-w // (8 * unit)) * (8 * unit)
        if w8 < self.n_pad:
            w = w8
        return w if w < self.n_pad else 0

    @contract.rank_uniform
    def _bag_compact_rows(self) -> int:
        """The active compacted window (rows per device shard under the
        sharded fused step; all rows otherwise).  0 = masked path."""
        if self._bag_window is None:
            self._bag_window = self._compute_bag_window()
        return 0 if self._bag_overflowed else self._bag_window

    @contract.rank_uniform
    def _bag_window_overflow(self) -> bool:
        """Host-side guard for the sharded per-shard window: True when
        the current draw's per-shard in-bag union count exceeds it
        (multi-host ORs the decision so every rank falls back
        together)."""
        union = self.bag_masks[0]
        for m in self.bag_masks[1:]:
            union = union | m
        if self._shard_layout is not None:
            union = self._shard_layout.place(union[:self.num_data],
                                             fill=False)
        counts = self.grower.shard_row_counts(union, self.n_pad)
        over = int(counts.max()) > self._bag_window
        if self._mh_fused:
            from ..parallel.dist import sync_max_ints
            over = bool(int(sync_max_ints([int(over)])[0]))
        return over

    def _ensure_bag_arranged(self) -> None:
        """Arrange device state in-bag-first when compaction is active
        and a re-bagging (or a general-path excursion) left it
        unarranged; no-op otherwise."""
        w = self._bag_compact_rows()
        if w <= 0 or self._bag_arranged:
            return
        if self._fused_sharded and self._bag_window_overflow():
            self._bag_overflowed = True
            log.warning(
                "bag_compact: a re-bagging draw overflowed the static "
                "per-shard window (%d rows); falling back to the masked "
                "full-sweep path for the rest of this run"
                % self._bag_window)
            return
        self._arrange_for_bag()
        self._bag_arranged = True

    def _dart_bank_rows(self) -> Optional[_FilledRows]:
        """Per-row DART bank buffers the arrangement must carry (base
        GBDT has none; DART returns its leaf bank and its fill)."""
        return None

    def _set_dart_bank_rows(self, arr) -> None:
        raise NotImplementedError   # only reachable from DART

    def _fused_gspecs(self, gstate):
        """PartitionSpecs of the fused gradient state: the objective's
        own query-sharded specs under the rank layout, else every leaf
        sharded on its last (row) axis."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS
        if self._layout_active:
            return self._gstate_specs
        return jax.tree_util.tree_map(
            lambda a: P(*([None] * (np.ndim(a) - 1) + [DATA_AXIS])),
            gstate)

    def _arrange_for_bag(self) -> None:
        """One device dispatch per re-bagging: stable-sort every per-row
        buffer in-bag-first, and inside the bag and out of it by the
        leaves of the trees grown last (_bag_arrange_body), so the fused
        step's static window holds every in-bag row in leaf order.  The
        result is 'just another row order', so metrics, checkpoints and
        the general-path restore reuse the existing ordered-partition
        machinery unchanged.  The class-wise path keys on the union bag
        alone: its rows follow the joint class key, which no packed tree
        row holds."""
        multi = self.num_class > 1
        L = max(self.config.num_leaves, 2)
        prev = () if multi else self._prev_trees()
        # the earlier trees whose leaves the key holds (zeros add none)
        keyed = min(len(self._prev_ints), len(prev),
                    31 // (L - 1).bit_length())
        if multi:
            mask = self._bag_masks_stacked_dev()
        else:
            mask = self._bag_mask_dev_fused(0)
            if mask.dtype == jnp.uint8:
                mask = _unpack_bag_jit(mask, self.n_pad)
        gstate = self._gstate_for_fused()
        order = (self._row_order if self._row_order is not None
                 else self._identity_order_dev())
        bank = self._dart_bank_rows()
        row_state = self.objective.make_row_state_fn()
        key = ("bag_arrange", multi, bank is not None, L,
               self.objective.fused_key(), self.dtype,
               id(self.grower.mesh) if self._fused_sharded else None)

        def make():
            if self._fused_sharded:
                return _make_bag_arrange_sharded(
                    row_state, multi, self.grower.mesh,
                    self._fused_gspecs(gstate), L)
            return _make_bag_arrange(row_state, multi, bank is not None, L)

        fn = _get_fused_step(key, make)
        args = (self.bins_dev, self.scores, mask, gstate, order, prev)
        moved = args[:3] + args[4:5]
        if bank is not None:
            args += (bank.rows, jnp.int32(bank.fill))
            moved += (bank,)
        with _enqueue("arrange", 0, self._shards,
                      window=self._bag_window, in_bag=self._bag_in_bag,
                      keyed=keyed,
                      **_resort_counts(moved, gstate, row_state)):
            out = fn(*args)
        self.bins_dev, self.scores, mask_new, gstate_new, order_new = \
            out[:5]
        if bank is not None:
            self._set_dart_bank_rows(out[5])
        if multi:
            self._bag_stacked = mask_new
        else:
            self._bag_dev_packed[0] = mask_new
        self._gstate_override = gstate_new
        self._row_order = order_new
        self._inv_order = None

    def _run_fused(self, bag_mask_dev, fmask_dev,
                   k_iters: int = 1) -> "List[_PendingTree]":
        """One fused dispatch covering k_iters boosting iterations
        (config.iter_batch; k_iters=1 is the per-iteration oracle).
        fmask_dev is [F] for k_iters=1 and [K, F] stacked otherwise;
        packed trees come back stacked and stay device-resident until
        the next flush."""
        cfg = self.config
        lr = self.shrinkage_rate
        # re-sort after the FIRST tree (clustering pays from tree 2 on),
        # then every reorder_every trees.  Segments with k_iters > 1 are
        # scheduled body-uniform (_plan_segment): either every iteration
        # re-sorts (reorder_every == 1) or none does.
        reorder = self._reorder_now()
        # bag compaction: the static window is live only while the
        # device state is actually arranged in-bag-first (the masked
        # full-sweep executable serves every other dispatch)
        compact = self._bag_compact_rows() if self._bag_arranged else 0
        gstate = self._gstate_for_fused()
        key = (self.objective.fused_key(), lr, self.dtype,
               self.hist_impl, self.max_bin, max(cfg.num_leaves, 2),
               cfg.max_depth, self.params, len(self.valid_bins_dev),
               self.hist_slots, self.hist_ranged,
               reorder, compact, k_iters,
               # sharded steps close over the mesh and the aggregation
               # protocol — two data-parallel configs that differ only
               # here MUST NOT share an executable
               (cfg.hist_agg, self.grower.num_shards,
                id(self.grower.mesh)) if self._fused_sharded else None)

        row_state = self.objective.make_row_state_fn()

        def make():
            grow_kw = self._grow_kw()
            if self._fused_sharded:
                from ..parallel.mesh import DATA_AXIS
                grow_kw.update(psum_axis=DATA_AXIS,
                               hist_agg=cfg.hist_agg,
                               num_shards=self.grower.num_shards,
                               voting_top_k=0)
                # query-sharded objectives carry their own specs (the
                # query-block leaves shard on their LEADING axis);
                # elementwise state shards on its last (row) axis
                return _make_fused_step_sharded(
                    self.objective.make_grad_fn(), grow_kw, lr,
                    self.dtype, self.grower.mesh,
                    len(self.valid_bins_dev),
                    self._fused_gspecs(gstate), reorder, row_state,
                    compact, k_iters)
            if reorder:
                return _make_fused_step_reorder(
                    self.objective.make_grad_fn(), grow_kw, lr,
                    self.dtype, row_state, compact, k_iters)
            return _make_fused_step(self.objective.make_grad_fn(),
                                    grow_kw, lr, self.dtype, compact,
                                    k_iters)

        fn = _get_fused_step(key, make)
        if reorder:
            # the reorder executable must see ONE bag-mask signature:
            # dispatches under an active row order pass the cached
            # ordered bool mask, so the first (identity-order) dispatch
            # unpacks its packed upload here — otherwise the second
            # re-sort retraces and recompiles the whole ~20s step with
            # bool[n] in place of u8[n/8] (observed as a mid-training
            # stall exactly at iteration hist_reorder_every+1)
            if bag_mask_dev.dtype == jnp.uint8:
                bag_mask_dev = _unpack_bag_jit(bag_mask_dev, self.n_pad)
            order = (self._row_order if self._row_order is not None
                     else self._identity_order_dev())
            prev_trees = self._prev_trees()
            with _enqueue("resort", k_iters, self._shards,
                          **_resort_counts(
                              [self.bins_dev, self.scores, bag_mask_dev,
                               order], gstate, row_state)):
                (scores, valid, ints, floats, bins_new, bag_new,
                 gstate_new, order_new, self._dev_stopped, _) = fn(
                    self.scores, list(self.valid_scores), bag_mask_dev,
                    fmask_dev, self.bins_dev, tuple(self.valid_bins_dev),
                    gstate, order, self._dev_stopped, prev_trees)
            self.bins_dev = bins_new
            self._bag_dev_packed[0] = bag_new
            self._gstate_override = gstate_new
            self._row_order = order_new
            self._inv_order = None
            self._trees_since_reorder = 0
        else:
            with _enqueue("scan", k_iters, self._shards):
                scores, valid, ints, floats, self._dev_stopped = fn(
                    self.scores, list(self.valid_scores), bag_mask_dev,
                    fmask_dev, self.bins_dev, tuple(self.valid_bins_dev),
                    gstate, self._dev_stopped)
            self._trees_since_reorder += k_iters
        self.scores = scores
        self.valid_scores = list(valid)
        # stacked [K, ...] rows stay unmaterialized device slices; the
        # deferred flush stacks every pending tree and pulls them in one
        # device_get
        pend = ([_PendingTree(ints, floats, lr, gated=True)] if k_iters == 1
                else [_PendingTree(ints[j], floats[j], lr, gated=True)
                      for j in range(k_iters)])
        self._prev_ints = (tuple(m.ints for m in pend[::-1])
                           + self._prev_ints)[:_RESORT_PREV]
        return pend

    def _prev_row(self, row) -> jax.Array:
        """A packed int row as the steps' own int rows lie: replicated
        over the mesh under the sharded step, on the device otherwise
        (one argument type, one trace)."""
        return (self.grower.replicate(row) if self._fused_sharded
                else jnp.asarray(row, jnp.int32))

    def _prev_trees(self) -> tuple:
        """The re-sorting step's `prev_trees`, and the bag arrangement's:
        the device int rows of the _RESORT_PREV trees the fused step grew
        last, the latest first, a row of zeros (no tree) for each it has
        not grown."""
        zero = self._prev_row(np.zeros(
            _packed_ints(max(self.config.num_leaves, 2)), np.int32))
        return (self._prev_ints + (zero,) * _RESORT_PREV)[:_RESORT_PREV]

    @contract.parity_oracle("the general per-tree path: one grow "
                            "dispatch per tree — the oracle every fused "
                            "path is parity-tested against (PARITY.md)")
    def _train_tree(self, grad, hess, bag_mask_dev, fmask, cls):
        cfg = self.config
        with _enqueue("general", 1, self._shards):   # one dispatch per tree
            if self.grower is not None and self._mh:
                # assemble process-local grad/hess into global sharded
                # arrays, grow SPMD across hosts, then pull the tree
                # (replicated) and this process's leaf_id block back to
                # local
                g = self.grower.shard_rows(
                    np.asarray(grad, dtype=self.dtype), self.n_pad)
                h = self.grower.shard_rows(
                    np.asarray(hess, dtype=self.dtype), self.n_pad)
                dev_tree, leaf_id = self.grower.grow(
                    self.bins_dev, g, h, bag_mask_dev,
                    self.grower.replicate(fmask))
                dev_tree = self.grower.replicated_to_local(dev_tree)
                leaf_id = self.grower.local_rows(leaf_id)
            elif self.grower is not None and self._feat_mh:
                # feature-parallel across hosts: rows replicated (every
                # process computes identical grad/hess on its full local
                # copy), features split; pull the replicated outputs
                # local
                g = self.grower.shard_rows(
                    np.asarray(grad, dtype=self.dtype), self.n_pad)
                h = self.grower.shard_rows(
                    np.asarray(hess, dtype=self.dtype), self.n_pad)
                dev_tree, leaf_id = self.grower.grow(
                    self.bins_dev, g, h, bag_mask_dev, fmask)
                dev_tree = self.grower.replicated_to_local(dev_tree)
                leaf_id = self.grower.local_replicated(leaf_id)
            elif self.grower is not None:
                dev_tree, leaf_id = self.grower.grow(
                    self.bins_dev, grad.astype(self.dtype),
                    hess.astype(self.dtype), bag_mask_dev,
                    jnp.asarray(fmask))
            else:
                dev_tree, leaf_id = grow_tree(
                    self.bins_dev,
                    grad.astype(self.dtype), hess.astype(self.dtype),
                    bag_mask_dev, jnp.asarray(fmask), **self._grow_kw())

        lr = self.shrinkage_rate
        # train-score update: leaf_value[leaf_id] gather for ALL rows —
        # covers both the reference's partition fast path and the
        # out-of-bag traversal (gbdt.cpp:162-167, score_updater.hpp:44-68).
        # Shrinkage multiplies in the hist dtype BEFORE the f32 cast, like
        # the reference's double leaf_value * rate then score_t cast.
        # (A 1-leaf stump has leaf_value[0] == 0, so this add is a no-op
        # for stopped trees — see _flush_pending.)
        leaf_vals = (dev_tree.leaf_value * lr).astype(jnp.float32)
        self.scores = self.scores.at[cls].add(leaf_vals[leaf_id])

        # validation scores via vectorized binned traversal, kept on device
        for i, vbins in enumerate(self.valid_bins_dev):
            vleaf = predict_leaf_binned(dev_tree.split_feature,
                                        dev_tree.threshold_bin,
                                        dev_tree.left_child,
                                        dev_tree.right_child, vbins)
            self.valid_scores[i] = (
                self.valid_scores[i].at[cls].add(leaf_vals[vleaf]))

        # Pack the tree into two flat device buffers; the next flush
        # stacks every pending tree and pulls them in one transfer, so
        # training never blocks on a per-iteration roundtrip.
        ints, floats = _pack_tree(dev_tree)
        return _PendingTree(ints, floats, lr)

    # -- lazy host materialization ------------------------------------
    @property
    def models(self) -> List[Tree]:
        """Host trees; materializes any pending device trees first."""
        self._flush_pending()
        return self._models

    @models.setter
    def models(self, value) -> None:
        self._models = list(value)

    @property
    def _shards(self) -> int:
        """Devices one training executable runs on (lgbm.enqueue)."""
        return 1 if self.grower is None else self.grower.num_shards

    def _exchange_bytes(self, leaves: int) -> Optional[int]:
        """Bytes ONE shard gave to histogram collectives for trees with
        `leaves` leaves in all (lgbm.flush): a tree reduces its root's
        histogram and each split's smaller child's, so one [F, B, 3]
        histogram a leaf under tree_learner=data (hist_agg=scatter pads
        F to the shard count).  0 without a grower; nothing for the
        voting and feature learners, whose exchange is not a histogram
        a leaf."""
        if self.grower is None:
            return 0
        if self.config.tree_learner != "data":
            return None
        f = self.train_data.num_features
        if self.config.hist_agg == "scatter":
            f = -(-f // self.grower.num_shards) * self.grower.num_shards
        return leaves * f * self.max_bin * 3 * np.dtype(self.dtype).itemsize

    def _sweep_grid(self) -> Tuple[int, int]:
        """(feature groups, block-diagonal matmuls) of ONE row step of a
        Pallas sweep over the bin matrix a shard holds (lgbm.flush):
        grid_rows x the first is the grid steps the flushed trees'
        block-list sweeps ran, x the second their matmuls.  Static, from
        F alone (ops/hist_pallas.py row_step); zeros off the kernels."""
        if self.hist_impl != "pallas":
            return 0, 0
        from ..ops.hist_pallas import row_step
        f = self.train_data.num_features
        if self.config.tree_learner == "feature":
            f = self.grower.padded_features(f) // self.grower.num_shards
        return row_step(f)

    @contract.counted_flush
    def _flush_pending(self) -> bool:
        """Unpack pending device trees; truncate at the first 1-leaf stump
        (the reference stops training there, gbdt.cpp:186).  Deleted trees
        that were NOT stumps (possible under changing bag/feature masks)
        have their score contributions subtracted so scores match the kept
        trees.  A multiclass stop mid-iteration keeps that iteration's
        earlier-class trees in the model AND in the scores even though
        prediction floors them away — exactly the reference's behavior
        (models_ keeps partials, gbdt.cpp:186-197; prediction floors
        num_used_model_ = size/num_class, gbdt.cpp:455,489).  Returns True
        when training must stop."""
        # ONE device_get for every pending tree, amortized over
        # _flush_every iterations: the copies of all their small buffers
        # start together and are awaited together.  (Until PR 33 the
        # buffers were stacked on the device first, two transfers in all;
        # but a stack compiles anew for every COUNT of pending trees, and a
        # job whose segments cross the flush boundary at varying places,
        # bagging epochs against the re-sort cadence, compiled one in its
        # steady state: inside a benchmark's window, which refuses it.)
        pending = [m for m in self._models if isinstance(m, _PendingTree)]
        if not pending:      # nothing to do, and no span: `models` asks
            return self._stopped                        # on every read
        pend = [m for m in pending if not isinstance(m.ints, np.ndarray)]
        with TraceAnnotation(
                spans.FLUSH, trees=len(pending),
                bytes=sum(m.ints.nbytes + m.floats.nbytes for m in pend)
                ) as flush_span:
            if pend:
                # explicit device_get: ONE counted call for the whole
                # batch (analysis/guards.py device_get accounting — bench
                # reports it as the per-tree sync metric)
                faultpoint("flush.device_get")
                with TraceAnnotation(spans.FLUSH_PULL):
                    ints_all, floats_all = jax.device_get(
                        ([m.ints for m in pend], [m.floats for m in pend]))
                for m, ih, fh in zip(pend, ints_all, floats_all):
                    m.ints, m.floats = ih, fh
            # a packed tree's first int is its leaf count, its last four
            # what its block-list sweeps and partition passes cost
            # (ops/grow.py TreeArrays)
            stats = {name: sum(int(m.ints[at]) for m in pending)
                     for at, name in ((-4, "blocks_swept"), (-3, "grid_rows"),
                                      (-2, "partition_blocks"),
                                      (-1, "rows_swept"))}
            stats["feat_groups"], stats["block_matmuls"] = self._sweep_grid()
            wire = self._exchange_bytes(sum(int(m.ints[0])
                                            for m in pending))
            if wire is not None:
                stats["exchange_bytes"] = wire
            if self.objective is not None:
                # what one tree's gradients cost (lambdarank's pair pass)
                stats.update(self.objective.trace_counters())
            stats.update(self._sampling_counters())
            stats.update(self._dart_counters())
            stats.update(self._class_counters())
            flush_span.set_metadata(**stats)
            with TraceAnnotation(spans.FLUSH_UNPACK):
                self._unpack_pending()
        if spans.stamp(spans.FIRST_TREE):
            # once a process: what the job's start cost (README.md)
            log.info(compile_cache.startup_line())
        return self._stopped

    def _sampling_counters(self) -> dict:
        """lgbm.flush's account of row and feature sampling, host-side
        and static or counted: the compacted window's rows (0 on the
        masked path), the newest bag's rows, the draws since the last
        flush, the features a tree may split on; each 0 where its
        sampling is off."""
        bagging = self.bagging_enabled
        frac = self.config.feature_fraction
        out = {"bag_window": self._bag_compact_rows() if bagging else 0,
               "bag_in_bag": self._bag_in_bag,
               "bag_draws": self._bag_draws,
               "feat_used": (int(self.train_data.num_features * frac)
                             if frac < 1.0 else 0)}
        self._bag_draws = 0
        return out

    def _dart_counters(self) -> dict:
        """lgbm.flush's account of dropout boosting: the trees dropped
        and, of them, replayed since the last flush, the trees banked
        and the bank's bound; 0 where the job is no DART job."""
        return {"dart_drops": 0, "dart_replayed": 0, "dart_bank_rows": 0,
                "dart_bank_cap": 0}

    def _class_counters(self) -> dict:
        """lgbm.flush's account of a class-wise job's sweeps: the classes,
        and the most and the fewest row blocks that one class's pending
        trees swept (class = the tree's index mod the classes; a packed
        tree's ints[-4] is its blocks_swept); 0 in a one-class job.  Read
        before the trees are unpacked."""
        k = self.num_class
        if k <= 1:
            return {"classes": 0, "class_blocks_max": 0,
                    "class_blocks_min": 0}
        blocks = [0] * k
        for idx, m in enumerate(self._models):
            if isinstance(m, _PendingTree):
                blocks[idx % k] += int(m.ints[-4])
        return {"classes": k, "class_blocks_max": max(blocks),
                "class_blocks_min": min(blocks)}

    def _unpack_pending(self) -> None:
        """_flush_pending's host half: pulled buffers -> host Trees,
        truncated at the first stump."""
        stop_at = None
        gated_flags = {}
        for idx, m in enumerate(self._models):
            if not isinstance(m, _PendingTree):
                continue
            gated_flags[idx] = m.gated
            tree = self._unpack_tree(m)
            self._models[idx] = tree
            if tree.num_leaves <= 1 and stop_at is None:
                stop_at = idx
        if stop_at is not None:
            for idx in range(stop_at, len(self._models)):
                t = self._models[idx]
                # fused-step trees past the stump were grown with the
                # device stopped flag set: their score updates were
                # already suppressed on device, nothing to subtract
                if t.num_leaves > 1 and not gated_flags.get(idx, False):
                    self._subtract_tree_scores(t, idx % self.num_class)
            del self._models[stop_at:]
            self._stopped = True
            self.num_used_model = len(self._models) // self.num_class
            self.iter = self.num_used_model

    def _subtract_tree_scores(self, tree: Tree, cls: int) -> None:
        """Remove a discarded tree's leaf values from train/valid scores
        (leaf assignment by binned traversal == the growth-time leaf_id;
        reverses _train_tree's adds to within one f32 ulp)."""
        self._add_tree_to_scores(tree, cls, -1.0, train=True, valid=True)

    def _add_tree_to_scores(self, tree: Tree, cls: int, scale: float,
                            train: bool, valid: bool) -> None:
        """Add scale * tree's (already-shrunk) leaf values to the train
        and/or valid score vectors via binned traversal on device.  Used
        by the stump-stop rollback and DART's drop/normalize cycle
        (dart.hpp:86-129)."""
        sf = jnp.asarray(tree.split_feature)
        tb = jnp.asarray(tree.threshold_bin)
        lc = jnp.asarray(tree.left_child)
        rc = jnp.asarray(tree.right_child)
        lv = jnp.asarray((tree.leaf_value * scale).astype(np.float32))
        if train:
            leaf = predict_leaf_binned(sf, tb, lc, rc, self.bins_dev)
            self.scores = self.scores.at[cls].add(lv[leaf])
        if valid:
            for i, vbins in enumerate(self.valid_bins_dev):
                vleaf = predict_leaf_binned(sf, tb, lc, rc, vbins)
                self.valid_scores[i] = (
                    self.valid_scores[i].at[cls].add(lv[vleaf]))

    def _unpack_tree(self, p: "_PendingTree") -> Tree:
        L = max(self.config.num_leaves, 2)
        ints = np.asarray(p.ints)
        floats = np.asarray(p.floats, dtype=np.float64)
        nl = int(ints[0])
        o = 1
        sf, tb, lc, rc, lp, ld, lcnt = (
            ints[o:o + L - 1], ints[o + L - 1:o + 2 * (L - 1)],
            ints[o + 2 * (L - 1):o + 3 * (L - 1)],
            ints[o + 3 * (L - 1):o + 4 * (L - 1)],
            ints[o + 4 * (L - 1):o + 4 * (L - 1) + L],
            ints[o + 4 * (L - 1) + L:o + 4 * (L - 1) + 2 * L],
            ints[o + 4 * (L - 1) + 2 * L:o + 4 * (L - 1) + 3 * L])
        sg = floats[:L - 1]
        lv = floats[L - 1:2 * L - 1]
        iv = floats[2 * L - 1:3 * L - 2]
        ds = self.train_data
        sf = sf[:nl - 1]
        tb = tb[:nl - 1]
        bounds = [ds.bin_mappers[f].bin_upper_bound for f in sf]
        threshold = np.array([bounds[i][tb[i]] for i in range(nl - 1)],
                             dtype=np.float64)
        tree = Tree(
            num_leaves=nl,
            split_feature=sf.copy(),
            split_feature_real=ds.real_feature_index[sf].astype(np.int32),
            threshold_bin=tb.copy(),
            threshold=threshold,
            split_gain=sg[:nl - 1],
            left_child=lc[:nl - 1],
            right_child=rc[:nl - 1],
            internal_value=iv[:nl - 1],
            leaf_parent=lp[:nl],
            leaf_value=lv[:nl],
            leaf_depth=ld[:nl],
            leaf_count=lcnt[:nl],
        )
        tree.shrinkage(p.lr)
        return tree

    def _inverse_row_order(self):
        """Device [n_pad] inverse permutation of the ordered-partition
        row order (cached between re-sorts), or None for identity."""
        if self._row_order is None:
            return None
        if self._inv_order is None:
            self._inv_order = (
                self.grower.inverse_order(self._row_order)
                if self._rows_local() else jnp.argsort(self._row_order))
        return self._inv_order

    def _rows_local(self) -> bool:
        """Single-host fused data-parallel: the row order keeps every
        shard's rows in its own block, so inverting it and taking rows by
        it are per-shard work (ShardedGrower.inverse_order, permute_rows)
        and not a sort and a gather of the whole array on each device."""
        return self._fused_sharded and not self._mh

    def _take_rows(self, arr, index):
        """arr[:, index] for a [K, n_pad] per-row array and an order or
        its inverse."""
        if self._rows_local():
            return self.grower.permute_rows(arr, index)
        return jnp.take(arr, index, axis=1)

    def _ensure_layout(self) -> None:
        """(Re-)place per-row state into the query-granular layout when
        the fused path resumes after a general-path excursion (custom
        gradients restore file order via _restore_row_order).  The
        initial placement happens in __init__; multi-host never comes
        back (the fused->general fallback is one-way there)."""
        if self._shard_layout is None or self._layout_active:
            return
        lay = self._shard_layout
        host = np.asarray(self.scores)[:, :self.num_data]
        self.scores = jnp.asarray(lay.place(host))
        if self.rows_sharded and not self._mh:
            self.scores = jax.device_put(self.scores,
                                         self.grower.row_sharding_2d())
        self.bins_dev = self.grower.shard_bins(
            lay.place(self.train_data.bins))
        self._bag_dev = [None] * self.num_class
        self._bag_dev_packed = [None] * self.num_class
        self._bag_stacked = None
        self._gstate_override = None
        self._layout_active = True

    def _layout_pos_dev(self):
        """Cached device copy of the layout's file-row -> padded-
        position map (reads scores back to file order without a host
        round trip)."""
        if getattr(self, "_layout_pos", None) is None:
            self._layout_pos = jnp.asarray(self._shard_layout.pos)
        return self._layout_pos

    def _unplace_host(self, arr: np.ndarray) -> np.ndarray:
        """Layout space -> file order + trailing pad (host, [.., n_pad])."""
        out = np.zeros_like(arr)
        filed = self._shard_layout.unplace(arr)
        out[..., :filed.shape[-1]] = filed
        return out

    def _restore_row_order(self) -> None:
        """Return all per-row state to FILE order (leaving the fused
        ordered-partition path and/or the query-granular layout: custom
        gradients, objective swaps)."""
        if self._mh_fused:
            # leaving the multi-host fused path (custom gradients): pull
            # this process's file-order block local and fall back to the
            # general per-tree path for the REST of training — one-way,
            # because the general path keeps scores process-local and
            # cannot hand them back to the global fused dispatch.
            # Materialize pending fused trees FIRST: their packed buffers
            # are REPLICATED global arrays, and a later flush would stack
            # them with the general path's process-local buffers
            # (incompatible devices); _stopped propagates via the next
            # flush either way.
            self._flush_pending()
            self.scores = jnp.asarray(self._mh_local_file_scores())
            self.valid_scores = [
                jnp.asarray(np.asarray(v.addressable_data(0)))
                for v in self.valid_scores]
            self.valid_bins_dev = [
                jnp.asarray(np.asarray(v.addressable_data(0)))
                for v in self.valid_bins_dev]
            self._dev_stopped = jnp.asarray(
                bool(np.asarray(self._dev_stopped.addressable_data(0))))
            if self._row_order is not None or self._layout_active:
                # rebuild the global sharded bins from FILE order: the
                # general mh path keeps using self.bins_dev, which the
                # ordered-partition re-sorts (and the query layout)
                # left permuted — training later trees on permuted bins
                # against file-order gradients would silently corrupt
                # every subsequent tree
                bins = self.train_data.bins
                if self.n_pad != self.num_data:
                    bins = np.pad(bins, ((0, 0),
                                         (0, self.n_pad - self.num_data)))
                self.bins_dev = self.grower.shard_bins(bins)
            self._layout_active = False
            self._shard_layout = None
            self._mh_fused = False
            self._fused_sharded = False
            # the general path has no device stopped flag: deferred
            # flushing is only sound without bagging/feature_fraction
            # (same recompute DART's _exit_bank_mode does)
            self._flush_every = (
                16 if (self.num_class == 1 and not self.bagging_enabled
                       and self.config.feature_fraction >= 1.0) else 1)
            self._bag_dev = [None] * self.num_class
            self._bag_dev_packed = [None] * self.num_class
            self._bag_stacked = None
            self._row_order = None
            self._inv_order = None
            self._gstate_override = None
            self._trees_since_reorder = self._unsorted_interval()
            self._bag_arranged = False
            return
        if self._row_order is None and not self._layout_active:
            return
        inv = self._inverse_row_order()
        if inv is not None:
            self.scores = self._take_rows(self.scores, inv)
        if self._layout_active:
            # query-granular layout -> file order + trailing pad (the
            # general path's convention); _ensure_layout re-places when
            # the fused path resumes
            s = jnp.take(self.scores, self._layout_pos_dev(), axis=1)
            self.scores = jnp.pad(
                s, ((0, 0), (0, self.n_pad - self.num_data)))
            self._layout_active = False
        bins = self.train_data.bins
        if self.n_pad != self.num_data:
            bins = np.pad(bins, ((0, 0), (0, self.n_pad - self.num_data)))
        self.bins_dev = jnp.asarray(bins)
        self._bag_dev = [None] * self.num_class
        self._bag_dev_packed = [None] * self.num_class
        self._bag_stacked = None
        self._row_order = None
        self._inv_order = None
        self._gstate_override = None
        self._trees_since_reorder = self._unsorted_interval()
        self._bag_arranged = False

    def _mh_local_base_scores(self) -> np.ndarray:
        """Multi-host fused: this process's [K, n_pad] block of the
        global row-sharded scores with any shard-local ordered-partition
        permutation undone (base layout space — file order + trailing
        pad for the default layout, query-granular blocks under the
        rank shard layout)."""
        s = np.asarray(self.grower.local_rows(self.scores))
        if self._row_order is not None:
            base = jax.process_index() * self.n_pad
            ordl = np.asarray(self.grower.local_rows(self._row_order)) \
                - base
            out = np.empty_like(s)
            out[:, ordl] = s
            s = out
        return s

    def _mh_local_file_scores(self) -> np.ndarray:
        """Multi-host fused: this process's [K, n_pad] block restored to
        FILE order (+ trailing pad)."""
        s = self._mh_local_base_scores()
        if self._layout_active:
            s = self._unplace_host(s)
        return s

    def _training_score(self):
        if self._mh_fused:
            s = self._mh_local_file_scores()[:, :self.num_data]
            return s[0] if self.num_class == 1 else s
        s = self.scores
        inv = self._inverse_row_order()
        if inv is not None:
            # ordered-partition mode keeps per-row state sorted by tree
            # leaves; metrics (and any external reader) see file order
            s = self._take_rows(s, inv)
        if self._layout_active:
            s = jnp.take(s, self._layout_pos_dev(), axis=1)
        s = s[:, :self.num_data]
        return s[0] if self.num_class == 1 else s

    def _score_for_gradients(self):
        """Padded scores handed to the objective (which is itself padded via
        pad_to, so no per-iteration slice/pad resharding round-trips); DART
        drops trees here first (GetTrainingScore override, dart.hpp:60-65)."""
        s = self.scores
        return s[0] if self.num_class == 1 else s

    # ------------------------------------------------------------------
    # multi-host: cli.init_train installs an OR-allreduce here so every
    # rank takes the same stop decision — a rank stopping alone would
    # deadlock the others' next SPMD collective (metrics are already
    # globally reduced, so decisions agree; this is the hard guarantee)
    stop_sync = None

    @contract.rank_uniform
    def _sync_stop(self, stop: bool) -> bool:
        if self.stop_sync is not None:
            return bool(self.stop_sync(bool(stop)))
        return stop

    def eval_and_check_early_stopping(self) -> bool:
        with TraceAnnotation(spans.EVAL, iter=self.iter):
            # Flush BEFORE evaluating: if a pending 1-leaf stump stopped
            # training, that stop wins — evaluating or popping trees off the
            # truncated model would corrupt it (the reference never reaches
            # its early-stopping path after the stump stop, gbdt.cpp:186).
            if self._sync_stop(self._flush_pending()):
                log.info("Stopped training because there are no more leafs "
                         "that meet the split requirements.")
                return True
            stop = self._sync_stop(self.output_metric(self.iter))
            if stop:
                log.info("Early stopping at iteration %d, the best iteration "
                         "round is %d"
                         % (self.iter, self.iter - self.early_stopping_round))
                for _ in range(self.early_stopping_round * self.num_class):
                    self.models.pop()
                self.num_used_model = len(self.models) // self.num_class
            return stop

    def output_metric(self, it: int) -> bool:
        """GBDT::OutputMetric (gbdt.cpp:231-267)."""
        cfg = self.config
        ret = False
        if it % cfg.metric_freq == 0:
            train_score = np.asarray(self._training_score())
            for metric in self.training_metrics:
                for name, val in zip(metric.names, metric.eval(train_score)):
                    log.info("Iteration: %d, %s : %f" % (it, name, val))
        if it % cfg.metric_freq == 0 or self.early_stopping_round > 0:
            for i in range(len(self.valid_metrics)):
                vs = np.asarray(self.valid_scores[i])
                score = vs[0] if self.num_class == 1 else vs
                for j, metric in enumerate(self.valid_metrics[i]):
                    vals = metric.eval(score)
                    if it % cfg.metric_freq == 0:
                        for name, val in zip(metric.names, vals):
                            log.info("Iteration: %d, %s : %f" % (it, name, val))
                    if not ret and self.early_stopping_round > 0:
                        cur = metric.factor_to_bigger_better * vals[-1]
                        if cur > self.best_score[i][j]:
                            self.best_score[i][j] = cur
                            self.best_iter[i][j] = it
                        elif it - self.best_iter[i][j] >= self.early_stopping_round:
                            ret = True
        return ret

    def get_eval_at(self, data_idx: int) -> List[float]:
        if data_idx == 0:
            score = np.asarray(self._training_score())
            return [v for m in self.training_metrics for v in m.eval(score)]
        i = data_idx - 1
        vs = np.asarray(self.valid_scores[i])
        score = vs[0] if self.num_class == 1 else vs
        return [v for m in self.valid_metrics[i] for v in m.eval(score)]

    # ------------------------------------------------------------------
    # prediction over raw feature values.  Default path: stacked-tree
    # device traversal (ops/predict.predict_leaf_stacked) in bounded row
    # chunks — the reference's whole-file host loop
    # (predictor.hpp:35-70) redesigned as data-parallel descents.  The
    # device routes with (hi, lo) f32 pair compares (f64-faithful, no
    # x64 needed); leaf-value accumulation happens on the host in f64,
    # so output formatting stays byte-identical to the reference under
    # any backend configuration.
    PREDICT_CHUNK = 1 << 17
    # matmul predictor: trees per scan block and rows per chunk (the
    # [C, tb*M, 4] selection temporary bounds memory)
    PREDICT_TREE_BLOCK = 8
    PREDICT_MM_CHUNK = 1 << 16
    PREDICT_INFLIGHT = 8

    def _stacked_trees(self, nmodels: int):
        """Padded [T, M]/[T, L] arrays for the first nmodels trees,
        cached until the model list grows."""
        from ..ops.predict import split_hi_lo
        # keyed on iter too: DART renormalizes EXISTING trees' leaf values
        # in place between iterations (dart.hpp Normalize), so a pack from
        # an earlier iteration would be stale
        key = (nmodels, self.iter)
        cached = getattr(self, "_stack_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        trees = self.models[:nmodels]
        max_l = max(t.num_leaves for t in trees)
        m = max(1, max_l - 1)
        sf = np.zeros((nmodels, m), dtype=np.int32)
        thr = np.zeros((nmodels, m), dtype=np.float64)
        lc = np.full((nmodels, m), -1, dtype=np.int32)
        rc = np.full((nmodels, m), -1, dtype=np.int32)
        lv = np.zeros((nmodels, max_l), dtype=np.float64)
        for i, t in enumerate(trees):
            ni = t.num_leaves - 1
            if ni > 0:
                sf[i, :ni] = t.split_feature_real[:ni]
                thr[i, :ni] = t.threshold[:ni]
                lc[i, :ni] = t.left_child[:ni]
                rc[i, :ni] = t.right_child[:ni]
            # ni == 0 keeps lc[0] == -1 == ~0: every row lands in leaf 0
            lv[i, :t.num_leaves] = t.leaf_value[:t.num_leaves]
        th, tl = split_hi_lo(thr)
        dev = tuple(jnp.asarray(a) for a in (sf, th, tl, lc, rc))
        # the matmul-predictor pack builds LAZILY (first accelerator
        # predict): CPU-only runs never pay its DFS/uploads
        pack = {"dev": dev, "lv": lv, "mm": None, "mm_built": False,
                "np": (trees, sf, th, tl, lc, rc, max_l, m)}
        self._stack_cache = (key, pack)
        return pack

    def _matmul_cached(self, pack):
        if not pack["mm_built"]:
            pack["mm"] = self._matmul_pack(*pack["np"])
            pack["mm_built"] = True
        return pack["mm"]

    def _matmul_pack(self, trees, sf, th, tl, lc, rc, max_l, m):
        """Device pack for the gather-free matmul predictor
        (ops/predict.predict_leaf_matmul).  Host-side array construction
        is SHARED with the serving forest (ops/predict.
        matmul_host_arrays) so the two packs cannot drift."""
        from ..ops.predict import matmul_host_arrays
        host = matmul_host_arrays(trees, sf, th, tl, lc, rc, max_l, m,
                                  self.max_feature_idx + 1,
                                  self.PREDICT_TREE_BLOCK)
        if host is None:
            return None
        tables, sel, thr_code, pos, neg, depth = host
        return (tables, (jnp.asarray(sel), jnp.asarray(thr_code),
                         jnp.asarray(pos), jnp.asarray(neg),
                         jnp.asarray(depth)))

    def _predict_leaves(self, x: np.ndarray, nmodels: int) -> np.ndarray:
        """[N, F] raw values -> [N, T] i32 leaf indices on device,
        chunked so memory stays bounded.

        Two kernels, same exact f64 routing semantics: accelerators take
        the gather-free matmul predictor (pointer-chasing descents cost
        one serialized gather per level per tree on TPU — measured 9x
        SLOWER than host numpy at 1Mx20; the matmul form runs on the
        MXU); CPU keeps the while-loop descent (XLA CPU handles the
        gathers fine and skips the O(C*M) compare work)."""
        from ..ops.predict import (predict_leaf_matmul,
                                   predict_leaf_stacked, rank_encode,
                                   split_hi_lo)
        x = np.asarray(x, dtype=np.float64)
        want = self.max_feature_idx + 1
        if x.shape[1] < want:
            # absent trailing features read as 0.0, the reference's
            # missing-value convention (predictor.hpp feature buffer) —
            # a narrow matrix must not silently gather-clamp on device
            x = np.pad(x, ((0, 0), (0, want - x.shape[1])))
        elif x.shape[1] > want:
            x = x[:, :want]
        pack = self._stacked_trees(nmodels)
        dev = pack["dev"]
        mm = (self._matmul_cached(pack)
              if jax.default_backend() != "cpu" else None)
        use_mm = mm is not None
        step = self.PREDICT_MM_CHUNK if use_mm else self.PREDICT_CHUNK
        n = x.shape[0]
        out = np.empty((n, nmodels), dtype=np.int64)

        def per_chunk(chunk):
            xh, xl = split_hi_lo(chunk)
            if use_mm:
                tables, mm_dev = mm
                code = rank_encode(xh, xl, tables)
                return predict_leaf_matmul(
                    *mm_dev, jnp.asarray(code),
                    tree_block=self.PREDICT_TREE_BLOCK)
            return predict_leaf_stacked(*dev, jnp.asarray(xh),
                                        jnp.asarray(xl))

        def write(a, rows, got):
            got = got[:rows]
            out[a:a + rows] = got[:, :nmodels] if use_mm else got

        self._predict_pipeline(x, step, per_chunk, write)
        return out

    def _predict_pipeline(self, x, step, per_chunk, write) -> None:
        """Bounded-in-flight chunk dispatch shared by the predict paths:
        the device pipelines chunk k+1 while chunk k's result reads
        back, but device buffers stay O(window), not O(N).  Rows pad up
        to a power-of-two bucket: one
        compiled executable per bucket instead of per distinct batch
        size.  per_chunk(padded_chunk) -> device array; write(a, rows,
        host_array) consumes results in order."""
        pending = []

        def drain(limit):
            while len(pending) > limit:
                a, rows, dev_res = pending.pop(0)
                write(a, rows, np.asarray(dev_res))

        n = x.shape[0]
        for a in range(0, n, step):
            chunk = np.ascontiguousarray(x[a:a + step])
            rows = chunk.shape[0]
            bucket = 256
            while bucket < rows:
                bucket <<= 1
            if bucket > rows:
                chunk = np.pad(chunk, ((0, bucket - rows), (0, 0)))
            pending.append((a, rows, per_chunk(chunk)))
            drain(self.PREDICT_INFLIGHT)
        drain(0)

    def predict_raw(self, x: np.ndarray) -> np.ndarray:
        """x [N, num_total_features] -> [K, N] raw scores."""
        k = self.num_class
        n = x.shape[0]
        nmodels = self.num_used_model * k
        if nmodels == 0 or n == 0:
            return np.zeros((k, n), dtype=np.float64)
        if jax.default_backend() != "cpu" and jax.config.jax_enable_x64:
            # fuse the f64 accumulation into the device dispatch: the
            # [C, T] leaf-index readback collapses to [K, C] doubles,
            # bit-identically (ops/predict.accumulate_scores replays
            # the host loop)
            out = self._predict_raw_device(x, nmodels)
            if out is not None:
                return out
        leaves = self._predict_leaves(x, nmodels)
        lv = self._stacked_trees(nmodels)["lv"]
        out = np.zeros((k, n), dtype=np.float64)
        # per-tree f64 accumulation in boosting order, exactly the
        # reference predictor's += tree->Predict (predictor.hpp:35-70)
        for i in range(nmodels):
            out[i % k] += lv[i, leaves[:, i]]
        return out

    def _predict_raw_device(self, x: np.ndarray,
                            nmodels: int) -> "Optional[np.ndarray]":
        """Chunked matmul-predictor leaves + on-device f64 accumulation;
        None when the matmul pack declines (wide features / code
        overflow), falling back to the leaf-readback path."""
        from ..ops.predict import (accumulate_scores, predict_leaf_matmul,
                                   rank_encode, split_hi_lo)
        x = np.asarray(x, dtype=np.float64)
        want = self.max_feature_idx + 1
        if x.shape[1] < want:
            x = np.pad(x, ((0, 0), (0, want - x.shape[1])))
        elif x.shape[1] > want:
            x = x[:, :want]
        pack = self._stacked_trees(nmodels)
        mm = self._matmul_cached(pack)
        if mm is None:
            return None
        if "lv_dev" not in pack or pack["lv_dev"] is None:
            pack["lv_dev"] = jnp.asarray(pack["lv"], dtype=jnp.float64)
        lv_dev = pack["lv_dev"]
        if lv_dev.dtype != jnp.float64:   # x64 actually off: not exact
            pack["lv_dev"] = None
            return None
        k = self.num_class
        n = x.shape[0]
        out = np.zeros((k, n), dtype=np.float64)
        tables, mm_dev = mm

        def per_chunk(chunk):
            xh, xl = split_hi_lo(chunk)
            code = rank_encode(xh, xl, tables)
            leaves = predict_leaf_matmul(
                *mm_dev, jnp.asarray(code),
                tree_block=self.PREDICT_TREE_BLOCK)
            return accumulate_scores(leaves[:, :nmodels], lv_dev,
                                     num_class=k)

        def write(a, rows, scores):
            out[:, a:a + rows] = scores[:, :rows]

        self._predict_pipeline(x, self.PREDICT_MM_CHUNK, per_chunk, write)
        return out

    def predict(self, x: np.ndarray) -> np.ndarray:
        raw = self.predict_raw(x)
        if self.sigmoid > 0:
            return 1.0 / (1.0 + np.exp(-2.0 * self.sigmoid * raw))
        if self.num_class > 1:
            e = np.exp(raw - raw.max(axis=0, keepdims=True))
            return e / e.sum(axis=0, keepdims=True)
        return raw

    def predict_leaf_index(self, x: np.ndarray) -> np.ndarray:
        k = self.num_class
        nmodels = self.num_used_model * k
        n = x.shape[0]
        if nmodels == 0 or n == 0:
            return np.zeros((n, nmodels), dtype=np.int64)
        return self._predict_leaves(x, nmodels)

    def set_num_used_model(self, num: int) -> None:
        if num >= 0:
            self.num_used_model = min(num // self.num_class,
                                      len(self.models) // self.num_class)

    # ------------------------------------------------------------------
    def save_model_to_file(self, num_used_model: int, is_finish: bool,
                           filename: str) -> None:
        """Incremental-append save (gbdt.cpp:351-400): holds back the last
        early_stopping_round trees until finish."""
        if self.saved_upto < 0:
            # atomic incremental save (resilience/atomic): trees stream
            # to a sibling tmp across segments; the finish commit
            # fsync+renames it into place, so a crash at ANY iteration
            # leaves the previous complete model file, never a
            # truncated one
            self._model_file = text_writer(filename)
            f = self._model_file
            f.write(self.name + "\n")
            f.write("num_class=%d\n" % self.num_class)
            f.write("label_index=%d\n" % self.label_idx)
            f.write("max_feature_idx=%d\n" % self.max_feature_idx)
            if self.objective is not None:
                f.write("objective=%s\n" % self.objective.name)
            f.write("sigmoid=%g\n" % self.sigmoid)
            f.write("\n")
            self.saved_upto = 0
        if self._model_file is None:
            return
        f = self._model_file
        if num_used_model == NO_LIMIT:
            num_used_model = len(self.models)
        else:
            num_used_model = num_used_model * self.num_class
        rest = num_used_model - self.early_stopping_round * self.num_class
        for i in range(self.saved_upto, rest):
            f.write("Tree=%d\n" % i)
            f.write(self.models[i].to_string() + "\n")
        self.saved_upto = max(self.saved_upto, rest)
        f.flush()
        if is_finish:
            for i in range(self.saved_upto, num_used_model):
                f.write("Tree=%d\n" % i)
                f.write(self.models[i].to_string() + "\n")
            f.write("\n" + self.feature_importance() + "\n")
            f.close()
            self._model_file = None

    def abort_model_save(self) -> None:
        """Discard an in-progress incremental save (graceful
        preemption): the sibling tmp is removed instead of orphaned,
        and the previously committed model file stays untouched."""
        if self._model_file is not None:
            self._model_file.abort()
            self._model_file = None
        self.saved_upto = -1

    def feature_importance(self) -> str:
        """Split-count importances (gbdt.cpp:458-485).  The reference
        orders ties among equal counts by non-stable std::sort; the native
        helper reruns that exact sort so the footer is byte-identical
        (falls back to a stable sort without the toolchain)."""
        imp = np.zeros(self.max_feature_idx + 1, dtype=np.int64)
        for tree in self.models:
            for s in tree.split_feature_real[:tree.num_leaves - 1]:
                imp[s] += 1
        names = (self.train_data.feature_names if self.train_data is not None
                 else ["Column_%d" % i for i in range(len(imp))])
        pairs = [(imp[i], names[i]) for i in range(len(imp)) if imp[i] > 0]
        from .. import native
        perm = native.sort_importance(np.asarray([p[0] for p in pairs]))
        if perm is not None:
            pairs = [pairs[i] for i in perm]
        else:
            pairs.sort(key=lambda p: -p[0])
        out = ["", "feature importances:"]
        out += ["%s=%d" % (name, cnt) for cnt, name in pairs]
        return "\n".join(out) + "\n"

    # -- exact-state checkpointing (superset of the reference, whose only
    # resume path re-boosts from predicted init scores and restarts the
    # bagging/feature RNG streams — SURVEY.md §5 checkpoint/resume) -----
    _TREE_FIELDS = ("split_feature", "split_feature_real", "threshold_bin",
                    "threshold", "split_gain", "left_child", "right_child",
                    "internal_value", "leaf_parent", "leaf_value",
                    "leaf_depth", "leaf_count")

    def save_checkpoint(self, path: str) -> None:
        """Snapshot the FULL trainer state: exact tree arrays (NOT the
        lossy 6-digit text format), score vectors, bagging masks,
        early-stopping bookkeeping and mt19937 stream positions.
        Resuming from it continues training bit-for-bit."""
        self._flush_pending()
        # ordered-partition mode keeps scores leaf-sorted; checkpoints
        # store FILE order plus the row order itself, so a restored
        # booster reconstructs the exact permuted state and resumes
        # bit-for-bit
        if self._mh_fused:
            # multi-host fused: each process snapshots ITS file-order
            # block (plus its local slice of the global row order below)
            scores = self._mh_local_file_scores()
        else:
            scores = np.asarray(self.scores)
            inv = self._inverse_row_order()
            if inv is not None:
                scores = scores[:, np.asarray(inv)]
            if self._layout_active:
                # checkpoints always store FILE order (+ trailing pad);
                # load_checkpoint re-places into the layout
                scores = self._unplace_host(scores)
        arrays = {
            "iter": np.int64(self.iter),
            "num_used_model": np.int64(self.num_used_model),
            "stopped": np.int64(self._stopped),
            "scores": scores,
            "bag_masks": np.stack(self.bag_masks),
            "num_valid_sets": np.int64(len(self.best_iter)),
            "num_trees": np.int64(len(self._models)),
            # bag compaction: whether the stored row order is the
            # in-bag-first arrangement of the stored masks (resume must
            # not re-arrange an already-arranged epoch), and whether a
            # sharded window overflow pinned this run to the masked path
            "bag_arranged": np.int64(self._bag_arranged),
            "bag_overflowed": np.int64(self._bag_overflowed),
        }
        if self._row_order is not None:
            arrays["row_order"] = (
                np.asarray(self.grower.local_rows(self._row_order))
                if self._mh_fused else np.asarray(self._row_order))
            arrays["trees_since_reorder"] = np.int64(
                self._trees_since_reorder)
        if self._prev_ints:
            # the next re-sort orders each leaf's rows by these trees'
            arrays["prev_trees"] = np.stack([
                np.asarray(self.grower.replicated_to_local(r)
                           if self._mh_fused else r)
                for r in self._prev_ints])
        # per-valid-set keys: metric counts can differ between valid sets,
        # so one rectangular [sets, metrics] array would be ragged
        for i in range(len(self.best_iter)):
            arrays["best_iter_%d" % i] = np.asarray(self.best_iter[i],
                                                    dtype=np.int64)
            arrays["best_score_%d" % i] = np.asarray(self.best_score[i],
                                                     dtype=np.float64)
        for t, tree in enumerate(self._models):
            arrays["tree%d_num_leaves" % t] = np.int64(tree.num_leaves)
            for f in self._TREE_FIELDS:
                arrays["tree%d_%s" % (t, f)] = np.asarray(getattr(tree, f))
        for i, vs in enumerate(self.valid_scores):
            arrays["valid_scores_%d" % i] = np.asarray(vs)
        for name, rng in self._rng_streams():
            arrays[name] = rng.get_state()
        # config/dataset binding: load_checkpoint (and resume=auto's
        # snapshot validation) reject a snapshot whose run this booster
        # does not continue — shape-coincident state under changed
        # hyper-parameters would otherwise resume silently wrong
        arrays["resume_fp"] = np.array(resume_fingerprint(self))
        arrays.update(self._extra_checkpoint_arrays())
        # atomic + sha256-footered write (resilience/atomic.write_npz
        # keeps the exact path — a direct savez would append .npz to a
        # bare name, and a crash mid-write would leave a truncated
        # archive that poisons the next resume)
        write_npz(path, arrays)

    def _extra_checkpoint_arrays(self) -> dict:
        """Subclass hook: extra state for save_checkpoint (DART's device
        tree bank)."""
        return {}

    def _restore_extra_checkpoint(self, z) -> None:
        """Subclass hook: restore _extra_checkpoint_arrays state."""

    def load_checkpoint(self, path: str) -> None:
        """Restore a save_checkpoint snapshot into a booster built with
        the same config and datasets.  Raises
        resilience.atomic.IntegrityError on a corrupt/truncated
        snapshot (footer-less archives from older versions load
        unverified)."""
        z = read_npz(path)
        if "resume_fp" in z.files:
            want, have = str(z["resume_fp"]), resume_fingerprint(self)
            if want != have:
                z.close()
                log.fatal("checkpoint %s was written under a different "
                          "config/dataset (%s) — loading it would "
                          "silently continue the OLD run; delete the "
                          "snapshot or restore the original config"
                          % (path, fingerprint_diff(want, have)))
        self.iter = int(z["iter"])
        self._stopped = bool(z["stopped"])
        self._dev_stopped = (
            self.grower.replicate(np.asarray(self._stopped))
            if self._mh_fused else jnp.asarray(self._stopped))
        # checkpointed per-row state is in FILE order; when the snapshot
        # carries an ordered-partition row order, rebuild the exact
        # permuted state (bins/scores/objective state) so training
        # resumes bit-for-bit on the same accumulation order.  "Base"
        # space below = file order + trailing pad, or the query-granular
        # layout blocks when the rank shard layout is configured (the
        # row order permutes base positions in both cases).
        lay = self._shard_layout
        bins = self.train_data.bins if self.train_data is not None else None
        if bins is not None:
            if lay is not None:
                bins = lay.place(bins)
            elif self.n_pad != self.num_data:
                bins = np.pad(bins,
                              ((0, 0), (0, self.n_pad - self.num_data)))
        z_file = np.asarray(z["scores"])
        if lay is not None:
            self._layout_active = True
            z_base = lay.place(z_file[:, :self.num_data])
        else:
            z_base = z_file
        ordl = None     # this process's local base-space permutation
        if "row_order" in z:
            order = np.asarray(z["row_order"])
            self._trees_since_reorder = int(z["trees_since_reorder"])
            if self._mh_fused:
                # the snapshot holds THIS process's slice of the global
                # order (global positions); rebuild host-side in local
                # coordinates, then assemble the global arrays
                ordl = order - jax.process_index() * self.n_pad
                self._row_order = self.grower.shard_rows(
                    order.astype(np.int32), self.n_pad)
                self.bins_dev = self.grower.shard_bins(bins[:, ordl])
                self._gstate_override = self._restored_gstate(ordl)
                z_scores = z_base[:, ordl]
            else:
                ordl = order
                self._row_order = jnp.asarray(order, dtype=jnp.int32)
                self.bins_dev = jnp.asarray(bins[:, order])
                self._gstate_override = self._restored_gstate(ordl)
                z_scores = z_base[:, order]
            bag_restored = True
        else:
            if bins is not None and (self._row_order is not None
                                     or lay is not None):
                self.bins_dev = (self.grower.shard_bins(bins)
                                 if self._mh_fused or lay is not None
                                 else jnp.asarray(bins))
            self._row_order = None
            self._trees_since_reorder = self._unsorted_interval()
            self._gstate_override = None
            z_scores = z_base
            bag_restored = False
        self._inv_order = None
        self._prev_ints = (tuple(self._prev_row(r) for r in z["prev_trees"])
                           if "prev_trees" in z else ())
        if self._mh_fused:
            self.scores = self.grower.shard_rows(z_scores, self.n_pad)
        else:
            self.scores = jnp.asarray(z_scores)
            if self.grower is not None and self.rows_sharded \
                    and not self._mh:
                self.scores = jax.device_put(
                    self.scores, self.grower.row_sharding_2d())
        self.bag_masks = [m.copy() for m in z["bag_masks"]]
        self._bag_in_bag = (int(np.count_nonzero(self.bag_masks[-1]))
                            if self.bagging_enabled else 0)
        self._bag_dev = [None] * self.num_class
        self._bag_dev_packed = [None] * self.num_class
        self._bag_stacked = None
        self._bag_arranged = bool(z["bag_arranged"]) \
            if "bag_arranged" in z else False
        self._bag_overflowed = bool(z["bag_overflowed"]) \
            if "bag_overflowed" in z else False
        if bag_restored:
            # the fused-path device bag mask must follow the restored row
            # order (host bag_masks stay in file order like everything host)
            bag_base = self.bag_masks[0]
            if lay is not None:
                bag_base = lay.place(bag_base[:self.num_data], fill=False)
            bag_ordered = bag_base[ordl]
            self._bag_dev_packed[0] = (
                self.grower.shard_rows(bag_ordered, self.n_pad)
                if self._mh_fused else jnp.asarray(bag_ordered))
        if "num_valid_sets" in z:
            nv = int(z["num_valid_sets"])
            self.best_iter = [[int(v) for v in z["best_iter_%d" % i]]
                              for i in range(nv)]
            self.best_score = [[float(v) for v in z["best_score_%d" % i]]
                               for i in range(nv)]
        else:   # 0.1.0 checkpoints: one rectangular [sets, metrics] array
            self.best_iter = [list(map(int, r)) for r in z["best_iter"]]
            self.best_score = [list(map(float, r)) for r in z["best_score"]]
        vput = (self.grower.replicate if self._mh_fused else jnp.asarray)
        for i in range(len(self.valid_scores)):
            self.valid_scores[i] = vput(z["valid_scores_%d" % i])
        for name, rng in self._rng_streams():
            rng.set_state(z[name])
        self._models = []
        for t in range(int(z["num_trees"])):
            fields = {f: z["tree%d_%s" % (t, f)].copy()
                      for f in self._TREE_FIELDS}
            self._models.append(Tree(
                num_leaves=int(z["tree%d_num_leaves" % t]), **fields))
        # honor a SetNumUsedModel cap active at checkpoint time
        self.num_used_model = min(int(z["num_used_model"]),
                                  len(self._models) // self.num_class)
        self._restore_extra_checkpoint(z)
        z.close()       # read_npz is lazy now: drop the archive's fd

    def _restored_gstate(self, ordl):
        """Gradient-state override matching a restored row order: the
        objective's permute fn over base state (elementwise), or the
        host-side per-shard permute of the query-sharded state (the
        re-sorts were shard-local, so the permutation applies block by
        block before the device put)."""
        if self._layout_active:
            host, specs = self._build_sharded_gstate_host()
            host = self.objective.permute_sharded_state_host(
                host, self._shard_layout, ordl)
            self._gstate_specs = specs
            return tuple(self.grower.put_spec(a, sp)
                         for a, sp in zip(host, specs))
        if not getattr(self.objective, "row_permutable", False):
            return None
        # a restored order is any permutation, not a sort's: the rows
        # follow it by a gather each, once a load
        rel = jnp.asarray(np.asarray(ordl), dtype=jnp.int32)
        rows, rebuild = self.objective.make_row_state_fn()(
            self.objective.grad_state())
        gs = rebuild([jnp.take(a, rel, axis=-1) for a in rows], rel)
        if self._mh_fused:
            gs = jax.tree_util.tree_map(
                lambda a: self.grower.shard_rows(np.asarray(a),
                                                 self.n_pad), gs)
        return gs

    def _rng_streams(self):
        out = [("bag_rng", self.bag_rng)]
        out += [("feat_rng_%d" % i, r) for i, r in enumerate(self.feat_rngs)]
        if hasattr(self, "drop_rng"):
            out.append(("drop_rng", self.drop_rng))
        return out

    def load_model_from_string(self, model_str: str) -> None:
        """GBDT::LoadModelFromString (gbdt.cpp:402-456).  Header + tree
        parsing is shared with the native predict fast path via
        models.tree.parse_model_text."""
        from .tree import parse_model_text

        header, trees = parse_model_text(model_str)
        self.num_class = header["num_class"]
        self.label_idx = header["label_index"]
        self.max_feature_idx = header["max_feature_idx"]
        if header["sigmoid"] is not None:
            self.sigmoid = header["sigmoid"]
        self.models = trees
        self.num_used_model = len(self.models) // self.num_class


class DART(GBDT):
    """Dropout boosting (reference src/boosting/dart.hpp).

    The serial single-class path with a traceable objective runs the
    BANKED fused iteration (_make_fused_step_dart): trees stay packed on
    device, the per-iteration drop/normalize score surgery happens
    in-dispatch, and host trees materialize from the async-copied
    as-trained rows plus an exact f64 replay of each tree's drop-factor
    history — no per-iteration host round trips and no drift from
    device-dtype compounding.  With the ordered partition on it re-sorts
    on the plain step's cadence, the leaf bank riding the re-sort.
    Multiclass, custom gradients and continued training keep the
    host-tree path."""
    name = "dart"

    # [N] buffers a step keeps replayed leaf ids in between its drop and
    # its normalise (a byte a row each; counted in the bank's budget)
    _REPLAY_SLOTS = 8

    def _build(self, config: Config, train_data, objective,
               training_metrics) -> None:
        super()._build(config, train_data, objective, training_metrics)
        self.drop_rate = config.drop_rate
        self.drop_rng = Mt19937Random(config.drop_seed)
        self.drop_index: List[int] = []
        self._drop_history: List[List[int]] = []
        self._bank = None           # [bank_ints [T+1, Li], bank_floats]
        self._bank_count = 0
        self._bank_disabled = False
        self._bank_dirty = False    # drop factors newer than host trees
        # per-row drop-factor history [(iteration, rate, k), ...]: the
        # host-side f64 record of every tree->Shrinkage chain the device
        # applied (in its own dtype) to the bank row
        self._bank_hist = {}
        self._bank_lv0 = {}         # row -> as-trained f64 leaf values
        self._dart_drops = 0        # since the last flush: lgbm.flush
        self._dart_replayed = 0
        # the banked path defers flushes like the fused GBDT paths; the
        # host-tree fallback needs trees (and the drop surgery) per
        # iteration
        self._flush_every = 16 if self._can_fuse_dart() else 1
        # (rows of the leaf bank, the last the dummy; replay slots)
        self._bank_plan = (self._plan_bank() if train_data is not None
                           and self._can_fuse_dart() else (0, 0))

    @contract.rank_uniform
    def _can_fuse_dart(self) -> bool:
        # objective check first: prediction-only instances return before
        # GBDT.__init__ sets grower/hist attributes
        return (getattr(self.objective, "jax_traceable", False)
                and self.num_class == 1
                and getattr(self, "grower", None) is None
                and not self._bank_disabled
                and self.objective.fused_key() is not None)

    def _compact_fusible(self) -> bool:
        # bag compaction attaches to the banked fused path; the
        # host-tree fallback keeps the masked oracle
        return self._can_fuse_dart()

    def _segment_fusible(self) -> bool:
        # iteration batching rides the banked path only (host-tree DART
        # needs per-iteration score surgery on host trees)
        return (self._can_fuse_dart()
                and (self._bank is not None or not self._models))

    def _reorder_enabled(self) -> bool:
        # the banked step re-sorts as the plain fused step does
        return (self.hist_ranged
                and getattr(self.objective, "row_permutable", False)
                and self._segment_fusible())

    def _train_segment_fused(self, k: int) -> None:
        self._run_fused_dart(k)

    def _bank_cap(self) -> int:
        """Trees the leaf bank can hold (its last row is the dummy)."""
        return max(self._bank_plan[0] - 1, 0)

    def _bank_fill(self) -> int:
        """Trees the leaf bank holds: those trained, up to its bound."""
        return min(self._bank_count, self._bank_cap())

    def _dart_bank_rows(self) -> Optional[_FilledRows]:
        """The leaf bank is per-row state: the in-bag-first arrangement
        must carry it (drop/normalize gathers read it by row position),
        as far as it is filled."""
        if self._bank is None:
            return None
        return _FilledRows(self._bank[2], self._bank_fill())

    def _set_dart_bank_rows(self, arr) -> None:
        self._bank[2] = arr

    def _startup_stats(self) -> dict:
        return {"bank_cap": self._bank_cap(),
                "bank_bytes": (self._bank_plan[0] * self.n_pad
                               * self._leaf_dtype().itemsize)}

    def _dart_counters(self) -> dict:
        out = {"dart_drops": self._dart_drops,
               "dart_replayed": self._dart_replayed,
               "dart_bank_rows": (self._bank_fill()
                                  if self._bank is not None else 0),
               "dart_bank_cap": self._bank_cap()}
        self._dart_drops = self._dart_replayed = 0
        return out

    def drop_history(self) -> List[List[int]]:
        """The trees each iteration so far dropped, in iteration order
        (the lottery over upstream's drop_seed stream; one forced where
        it dropped none), as bag_mask() is for bags: for whoever checks
        the job against upstream's stream."""
        return [list(d) for d in self._drop_history]

    def _score_for_gradients(self):
        self._dropping_trees()
        return super()._score_for_gradients()

    def _one_iter(self, gradients, hessians, is_eval: bool) -> bool:
        if (gradients is None and self._can_fuse_dart()
                and (self._bank is not None or not self._models)):
            return self._train_one_iter_banked(is_eval)
        self._exit_bank_mode()
        stopped = super()._one_iter(gradients, hessians, False)
        self._normalize()
        if stopped:
            return True
        if is_eval:
            return self.eval_and_check_early_stopping()
        return False

    # -- banked fused path ---------------------------------------------
    def _train_one_iter_banked(self, is_eval: bool) -> bool:
        self._run_fused_dart()
        self.iter += 1
        self.num_used_model = len(self._models) // self.num_class
        if self.iter % self._flush_every == 0 and not is_eval:
            if self._sync_stop(self._flush_pending()):
                log.info("Stopped training because there are no more "
                         "leafs that meet the split requirements.")
                return True
        if is_eval:
            return self.eval_and_check_early_stopping()
        return False

    def _draw_drops(self, it: int) -> None:
        """The drop lottery (dart.hpp:86-99) for iteration `it`, shared
        verbatim by both paths so the mt19937 stream stays golden-pinned.
        Pure host state (drop_rng position + `it`), so a K-iteration
        segment precomputes all K lotteries before the dispatch."""
        with TraceAnnotation(spans.DART_DRAW, iter=it) as span:
            self.drop_index = []
            if self.drop_rate > 1e-15:
                if it > 0:
                    draws = self.drop_rng.next_doubles(it)
                    self.drop_index = [i for i in range(it)
                                       if draws[i] < self.drop_rate]
            if not self.drop_index and it > 0:
                self.drop_index = list(self.drop_rng.sample(it, 1))
            self.shrinkage_rate = 1.0 / (1.0 + len(self.drop_index))
            span.set_metadata(k=len(self.drop_index))
        del self._drop_history[it:]     # a restored job draws again
        self._drop_history.append(list(self.drop_index))

    def _leaf_dtype(self):
        return np.dtype(np.uint8 if max(self.config.num_leaves, 2) <= 256
                        else np.int32)

    def _plan_bank(self) -> Tuple[int, int]:
        """(rows of the leaf bank, replay slots), from the device's
        memory limit and the shapes alone, once, at start-up.

        A banked tree is a leaf id a row ([N] uint8: 68 MB at 68.3M
        rows), so the bank of a 500-tree job would be 34 GB.  It holds
        what fits beside the job's own state: the bin matrix and some
        32 B a row of scores, order, bag, gradients and the objective's
        arrays, and what the largest step holds while it runs, the
        re-sort's stack of word rows padded to whole tiles and its sort,
        80 B a row at 68.3M x 39, with a sixteenth of the limit left
        over.  The re-sort's key adds the earlier trees' replayed ids: its
        step's temporaries read 5.73 GiB for a described v5e (90.0 B a
        row; PERF.md section 6), which the sixteenth holds (0.64 of its
        0.98 GiB), and a DART job with a bank of 63 trees peaked at
        9.41 GB of the chip's 16.91 (PERF.md section 6).  Whole groups of
        a re-sort's carry (_bank_group: 32 uint8 rows), the last row the
        one dead and unbanked steps write to; no more than the job's
        num_iterations need.  Where some tree will lie outside, the
        step's replay slots are counted too.  A bank that cannot hold
        one tree stops the job here, with the numbers."""
        from ..utils.device import memory_limit_bytes
        dt = self._leaf_dtype()
        per_word = 4 // dt.itemsize
        group = _TILE_WORDS * per_word
        need = max(self.config.num_iterations, 1) + 1    # + the dummy row
        whole = -(-need // per_word) * per_word if need < group \
            else -(-need // group) * group
        limit = memory_limit_bytes()
        if limit is None:
            log.info("DART: leaf bank of %d trees (no memory limit known)"
                     % (whole - 1))
            return whole, 0
        n, f = self.n_pad, int(self.bins_dev.shape[0])
        words = _word_rows(self.bins_dev, n) + 5
        live = n * (f * self.bins_dev.dtype.itemsize + 32)
        step = n * (4 * -(-words // _TILE_WORDS) * _TILE_WORDS + 16)
        room = limit - limit // 16 - live - step
        fit = room // (n * dt.itemsize)
        slots = 0
        if fit < whole:
            slots = self._REPLAY_SLOTS
            fit -= slots
            whole = fit // group * group if fit >= group \
                else fit // per_word * per_word
        told = ("DART: leaf bank of %d trees, %.2f GB (%d rows x %d B; the "
                "device lets a process hold %.2f GB, the job's rows %.2f, "
                "its largest step %.2f); a dropped tree past it is "
                "replayed" % (max(whole - 1, 0), whole * n * dt.itemsize
                              / 1e9, n, dt.itemsize, limit / 1e9,
                              live / 1e9, step / 1e9))
        if whole < 2:
            log.fatal(told + ": no room for one banked tree beside the "
                      "rows; train fewer rows a device")
        log.info(told)
        return whole, slots

    def _ensure_bank_capacity(self, k_iters: int) -> None:
        """Tree rows for the next k_iters trees (+ the dummy row dead
        steps write to); initializes on first use, doubles past
        config.num_iterations (api num_boost_round, bench loops).  The
        LEAF bank ([rows, N], _plan_bank) is made once, on the device,
        and never grows: no copy of it is ever held beside it, and a
        tree past it is replayed where it is dropped."""
        cfg = self.config
        L = max(cfg.num_leaves, 2)
        SF0, TB0, LC0, RC0, RC1, LV0, LV1 = _dart_layout(L)
        leaf_dt = self._leaf_dtype()
        if self._bank is None:
            T = max(cfg.num_iterations, k_iters) + 1  # + dummy row
            li = _packed_ints(L)
            lf = 3 * L - 2
            bi = np.zeros((T, li), np.int32)
            # untouched rows must TERMINATE traversal: child slots -1
            # (~0 = leaf 0, whose value is 0.0) instead of a node-0
            # self-loop
            bi[:, LC0:RC1] = -1
            self._bank = [jnp.asarray(bi),
                          jnp.zeros((T, lf), dtype=self.dtype),
                          jnp.zeros((self._bank_plan[0], self.n_pad),
                                    dtype=leaf_dt),
                          [jnp.zeros((T, int(vb.shape[1])), dtype=leaf_dt)
                           for vb in self.valid_bins_dev]]
            self._bank_count = 0
        while self._bank_count + k_iters > self._bank[0].shape[0] - 1:
            # double the small banks (a tree's packed rows, the valid
            # sets' ids), keeping new rows traversal-safe.  The OLD
            # dummy row becomes a real row — reset it too: dead
            # (post-stop) steps may have written a garbage tree there,
            # which would otherwise materialize as a phantom model entry
            T = self._bank[0].shape[0]
            safe = np.zeros((1, self._bank[0].shape[1]), np.int32)
            safe[:, LC0:RC1] = -1
            pad_i = np.repeat(safe, T, axis=0)

            def dbl(a):
                return jnp.concatenate(
                    [a, jnp.zeros((T,) + a.shape[1:], dtype=a.dtype)])

            self._bank = [
                jnp.concatenate([self._bank[0][:-1],
                                 jnp.asarray(safe), jnp.asarray(pad_i)]),
                dbl(self._bank[1].at[T - 1].set(0.0)),
                self._bank[2],
                [dbl(vb) for vb in self._bank[3]]]

    def _run_fused_dart(self, k_iters: int = 1) -> None:
        cfg = self.config
        L = max(cfg.num_leaves, 2)
        self._ensure_bank_capacity(k_iters)
        # per-iteration host inputs, drawn in the exact sequential order
        # (drop lottery -> bagging -> feature mask per iteration): drop
        # lists, 1/(1+k) shrinkages and normalization factors are pure
        # host/mt19937 state, so a K-segment precomputes them all and
        # feeds them as stacked [K, ...] scan inputs
        drops, rates, kfs, fmasks = [], [], [], []
        with TraceAnnotation(spans.HOST_INPUTS):
            for j in range(k_iters):
                it = self.iter + j
                self._draw_drops(it)
                kd = len(self.drop_index)
                # record this cycle's f64 factor pair against every dropped
                # row (replayed at materialization; entries from iterations
                # past a stump stop are filtered out there, matching the
                # device gating)
                for i in self.drop_index:
                    self._bank_hist.setdefault(i, []).append(
                        (it, self.shrinkage_rate, float(kd)))
                drops.append(list(self.drop_index))
                rates.append(self.shrinkage_rate)
                kfs.append(float(kd))
                self._bagging(it, 0)
                if j == 0:
                    self._ensure_bag_arranged()
                fmasks.append(self._feature_mask(0))
        reorder = self._reorder_now()
        compact = self._bag_compact_rows() if self._bag_arranged else 0
        slots = self._bank_plan[1]
        # the drop list is as long as the tree rows and its count a
        # run-time scalar: ONE executable a (re-sorting or not, K),
        # whatever an iteration drops
        drop_idx = np.zeros((k_iters, self._bank[0].shape[0]), np.int32)
        for j, d in enumerate(drops):
            drop_idx[j, :len(d)] = d
        drop_count = np.asarray([len(d) for d in drops], np.int32)
        self._dart_drops += int(drop_count.sum())
        self._dart_replayed += sum(i >= self._bank_cap()
                                   for d in drops for i in d)
        key = ("dart", self.objective.fused_key(), self.dtype,
               self.hist_impl, self.max_bin, L, cfg.max_depth,
               self.params, len(self.valid_bins_dev), self.hist_slots,
               self.hist_ranged, slots, reorder, compact, k_iters)
        row_state = self.objective.make_row_state_fn()

        def make():
            grow_kw = self._grow_kw()
            return _make_fused_step_dart(self.objective.make_grad_fn(),
                                         grow_kw, self.dtype, L, slots,
                                         row_state if reorder else None,
                                         compact, k_iters)

        fn = _get_fused_step(key, make)
        gstate = self._gstate_for_fused()
        with TraceAnnotation(spans.HOST_INPUTS):
            one = k_iters == 1
            rates_dev = np.asarray(rates, np.float64).astype(self.dtype)
            kfs_dev = np.asarray(kfs, np.float64).astype(self.dtype)
            t_row = np.arange(self._bank_count, self._bank_count + k_iters,
                              dtype=np.int32)
            fmask = np.stack(fmasks)
            bag_dev = self._bag_mask_dev_fused(0)
            if reorder and bag_dev.dtype == jnp.uint8:
                # ONE bag signature for the re-sorting step (_run_fused)
                bag_dev = _unpack_bag_jit(bag_dev, self.n_pad)
            args = (self.scores, list(self.valid_scores), self._bank[0],
                    self._bank[1], self._bank[2], list(self._bank[3]),
                    *(jnp.asarray(a[0] if one else a) for a in
                      (drop_idx, drop_count, rates_dev, kfs_dev)),
                    bag_dev, jnp.asarray(fmask[0] if one else fmask),
                    self.bins_dev, tuple(self.valid_bins_dev),
                    gstate, self._dev_stopped,
                    jnp.asarray(t_row[0] if one else t_row))
        if reorder:
            order = (self._row_order if self._row_order is not None
                     else self._identity_order_dev())
            fill = min(self._bank_count + k_iters, self._bank_cap())
            with _enqueue("resort", k_iters, self._shards,
                          **_resort_counts(
                              [self.bins_dev, self.scores, bag_dev, order,
                               _FilledRows(self._bank[2], fill)], gstate,
                              row_state)):
                (self.scores, valid, bi, bf, lb, vbs, ints, floats,
                 self._dev_stopped, self.bins_dev, bag_new, gstate_new,
                 self._row_order) = fn(*args, order)
            self._bag_dev_packed[0] = bag_new
            self._gstate_override = gstate_new
            self._inv_order = None
            self._trees_since_reorder = 0
        else:
            with _enqueue("dart", k_iters, self._shards):
                (self.scores, valid, bi, bf, lb, vbs, ints, floats,
                 self._dev_stopped) = fn(*args)
            self._trees_since_reorder += k_iters
        self._bank = [bi, bf, lb, list(vbs)]
        self.valid_scores = list(valid)
        # raw floats + each iteration's 1/(1+k) shrinkage applied on the
        # host in f64, like every other fused path
        pend = ([_PendingTree(ints, floats, rates[0], gated=True)]
                if k_iters == 1
                else [_PendingTree(ints[j], floats[j], rates[j], gated=True)
                      for j in range(k_iters)])
        self._models.extend(pend)
        # the trees a bag arrangement keys on (_prev_trees); the step's
        # own re-sort takes them from the bank
        self._prev_ints = (tuple(m.ints for m in pend[::-1])
                           + self._prev_ints)[:_RESORT_PREV]
        self._bank_count += k_iters
        self._bank_dirty = True

    def _materialize_bank(self) -> None:
        """Refresh every materialized tree's leaf values by replaying
        its recorded drop-factor chain in FLOAT64 from the as-trained
        values — exactly the host/reference tree->Shrinkage sequence
        (the device bank compounds the same chain in the histogram dtype
        for score updates only).  Runs after the base flush so new
        pending trees exist as host Trees; entries from iterations past
        a stump stop are excluded, matching the device's live gating."""
        if self._bank is None or not self._bank_dirty:
            return
        stop_iter = self.iter if self._stopped else float("inf")
        with TraceAnnotation(spans.FLUSH_UNPACK):
            for idx, tree in enumerate(self._models):
                lv0 = self._bank_lv0.get(idx)
                if lv0 is None:
                    lv0 = np.asarray(tree.leaf_value,
                                     dtype=np.float64).copy()
                    self._bank_lv0[idx] = lv0
                v = lv0.copy()
                for it, rate, k in self._bank_hist.get(idx, ()):
                    if it > stop_iter:
                        break
                    v *= -1.0
                    v *= rate
                    v *= -k
                tree.leaf_value = v
        self._bank_dirty = False

    def _flush_pending(self) -> bool:
        stopped = super()._flush_pending()
        self._materialize_bank()
        return stopped

    def _exit_bank_mode(self) -> None:
        """Leave the banked path permanently (custom gradients, objective
        swap, continued training): host trees become authoritative."""
        if self._bank_disabled:
            return
        if self._bank is not None:
            self._flush_pending()   # base flush + f64 replay
        self._bank = None
        self._bank_disabled = True
        self._flush_every = 1

    def _dropping_trees(self) -> None:
        """dart.hpp:86-110 on HOST trees (non-banked path): drop trees
        from the train score, set shrinkage."""
        self._draw_drops(self.iter)
        for i in self.drop_index:
            for cls in range(self.num_class):
                t = self.models[i * self.num_class + cls]
                t.shrinkage(-1.0)
                self._add_tree_to_scores(t, cls, 1.0, train=True, valid=False)

    def _normalize(self) -> None:
        """dart.hpp:114-129."""
        k = float(len(self.drop_index))
        for i in self.drop_index:
            for cls in range(self.num_class):
                t = self.models[i * self.num_class + cls]
                t.shrinkage(self.shrinkage_rate)
                self._add_tree_to_scores(t, cls, 1.0, train=False, valid=True)
                t.shrinkage(-k)
                self._add_tree_to_scores(t, cls, 1.0, train=True, valid=False)

    def save_model_to_file(self, num_used_model, is_finish, filename):
        # DART only saves once training finished (dart.hpp:71-76)
        if is_finish and self.saved_upto < 0:
            super().save_model_to_file(num_used_model, is_finish, filename)

    # -- checkpointing of the device bank ------------------------------
    def _extra_checkpoint_arrays(self) -> dict:
        """Bank state for exact banked resume: the (mutated) device rows,
        the drop-factor history and the as-trained leaf values the f64
        replay starts from.  Host-tree-path snapshots mark bank=0 and
        restore into the host path."""
        if self._bank is None:
            return {"dart_bank": np.int64(0)}
        out = {
            "dart_bank": np.int64(1),
            "dart_bank_count": np.int64(self._bank_count),
            "dart_bank_i": np.asarray(self._bank[0]),
            "dart_bank_f": np.asarray(self._bank[1]),
            "dart_bank_hist": np.asarray(
                [(r, it, rate, k)
                 for r, entries in sorted(self._bank_hist.items())
                 for (it, rate, k) in entries],
                dtype=np.float64).reshape(-1, 4),
            "dart_bank_lv0_rows": np.asarray(
                sorted(self._bank_lv0), dtype=np.int64),
            "dart_drop_iters": np.int64(len(self._drop_history)),
            "dart_drop_history": np.asarray(
                [(it, t) for it, d in enumerate(self._drop_history)
                 for t in d], dtype=np.int64).reshape(-1, 2),
        }
        if self._bank_lv0:
            out["dart_bank_lv0"] = np.stack(
                [self._bank_lv0[r] for r in sorted(self._bank_lv0)])
        return out

    def _restore_extra_checkpoint(self, z) -> None:
        if ("dart_bank" not in z or int(z["dart_bank"]) == 0
                or self.train_data is None):
            # host-tree-path snapshot (or a pre-bank version): resume
            # through the host path, whose trees the base restore rebuilt
            self._bank = None
            self._bank_disabled = True
            self._bank_hist = {}
            self._bank_lv0 = {}
            self._bank_dirty = False
            self._flush_every = 1
            return
        bank_i = jnp.asarray(np.asarray(z["dart_bank_i"]))
        bank_f = jnp.asarray(np.asarray(z["dart_bank_f"]),
                             dtype=self.dtype)
        self._bank_count = int(z["dart_bank_count"])
        if "dart_drop_history" in z:
            self._drop_history = [[] for _ in range(int(z["dart_drop_iters"]))]
            for it, t in np.asarray(z["dart_drop_history"]).reshape(-1, 2):
                self._drop_history[int(it)].append(int(t))
        # leaf-assignment banks are NOT checkpointed ([T, N] would dwarf
        # the snapshot); rebuild them with one traversal per restored
        # tree — structure is immutable, so this reproduces the training-
        # time leaf ids exactly, in the restored row order.  The leaf
        # bank is made on the device at its planned size and filled a row
        # at a time IN PLACE (donated), as far as it reaches: no [T, N]
        # buffer on the host, no second copy on the device.  The valid
        # sets' rows collect in host buffers and upload once.
        T = int(bank_i.shape[0])
        leaf_dt = self._leaf_dtype()
        lb = jnp.zeros((self._bank_plan[0], self.n_pad), dtype=leaf_dt)
        vbs = [np.zeros((T, int(vb.shape[1])), dtype=leaf_dt)
               for vb in self.valid_bins_dev]
        for t, tree in enumerate(self._models[:self._bank_count]):
            sf = jnp.asarray(tree.split_feature)
            tb = jnp.asarray(tree.threshold_bin)
            lc = jnp.asarray(tree.left_child)
            rc = jnp.asarray(tree.right_child)
            if t < lb.shape[0] - 1:
                lb = _set_bank_row(lb, t, predict_leaf_binned(
                    sf, tb, lc, rc, self.bins_dev))
            for i, vbins in enumerate(self.valid_bins_dev):
                vbs[i][t] = np.asarray(predict_leaf_binned(
                    sf, tb, lc, rc, vbins)).astype(leaf_dt)
        self._bank = [bank_i, bank_f, lb,
                      [jnp.asarray(vb) for vb in vbs]]
        self._bank_disabled = False
        self._bank_dirty = False      # restored trees hold final values
        hist = {}
        for r, it, rate, k in np.asarray(z["dart_bank_hist"]).reshape(-1, 4):
            hist.setdefault(int(r), []).append((int(it), float(rate),
                                                float(k)))
        self._bank_hist = hist
        rows = [int(r) for r in z["dart_bank_lv0_rows"]]
        self._bank_lv0 = (
            {r: np.asarray(z["dart_bank_lv0"])[i].copy()
             for i, r in enumerate(rows)} if rows else {})


def create_boosting(config: Config, train_data, objective,
                    training_metrics=()) -> GBDT:
    if config.boosting_type == "dart":
        return DART(config, train_data, objective, training_metrics)
    return GBDT(config, train_data, objective, training_metrics)


def boosting_type_from_model_file(path: str) -> str:
    """Sniff first line (reference src/boosting/boosting.cpp:7-16)."""
    with open(path) as f:
        first = f.readline().strip()
    return first if first in ("gbdt", "dart") else "gbdt"
