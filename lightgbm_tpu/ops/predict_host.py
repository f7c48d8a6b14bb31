"""Host-side exact-compare encodings shared by every predict route.

The ONE home of the order-isomorphic f64 encoding and the rank-encoded
pack builder: the device matmul predictor (ops/predict.py), the batch
predictor (models/gbdt.py) and the serving flat-table engine
(serving/flatforest.py) all build their threshold representations here,
so the three routes compare values against the SAME keys and cannot
drift.  Everything in this module is pure numpy — it is importable from
jax-free lanes (the low-latency serving fast path runs a backend=native
process that must never pull jax), and ops/predict.py re-exports the
names for its historical callers.
"""

from __future__ import annotations

__jax_free__ = True

from typing import List, Tuple

import numpy as np


def split_hi_lo(a: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Order-isomorphic encoding of f64 values as (hi, lo) uint32 pairs.

    The device never needs x64: each double's bit pattern is mapped on
    the HOST to a uint64 whose unsigned order equals the IEEE-754 total
    order (negatives bit-flipped, positives sign-bit-set — the classic
    radix-sortable-float transform), then split into two uint32 words.
    Lexicographic compare of the pairs reproduces the f64 `<=` EXACTLY
    for every finite value, ±1e308 (the parser's inf mapping), and
    subnormals — no precision loss, int ops only on device.  -0.0 is
    normalized to +0.0 first (IEEE `<=` treats them equal); NaN maps to
    the largest key, so `value <= threshold` is false and NaN rows take
    the right child, matching the reference's failed double compare
    (tree.h:179-189)."""
    # one mutable working copy + in-place bit math: the naive
    # np.where chain built ~5 full-size temporaries, which dominated
    # peak memory for wide chunks (sparse prediction)
    a = np.array(a, dtype=np.float64, copy=True)
    nan = np.isnan(a)
    np.copyto(a, 0.0, where=(a == 0.0))     # -0.0 -> +0.0
    neg = np.signbit(a)                     # bit-level sign (incl. -nan)
    bits = a.view(np.uint64)
    bits ^= np.uint64(0x8000000000000000)   # non-negatives: set sign bit
    bits[neg] ^= np.uint64(0x7FFFFFFFFFFFFFFF)  # negatives: full flip
    bits[nan] = np.uint64(0xFFFFFFFFFFFFFFFF)
    lo = bits.astype(np.uint32)             # u64 -> u32 keeps the low word
    bits >>= np.uint64(32)
    hi = bits.astype(np.uint32)
    return hi, lo


def order_key(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """(hi, lo) uint32 pair -> uint64 order key.  The ONE definition both
    the model pack (threshold ranks) and rank_encode (value codes) use —
    the matmul predictor's exactness rests on the two sides agreeing."""
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def rank_encode(hi: np.ndarray, lo: np.ndarray, tables: List[np.ndarray],
                dtype: "np.dtype" = np.uint16) -> np.ndarray:
    """Host-side exact rank encoding of raw values against the MODEL's
    per-feature threshold tables (prediction-time binning).

    tables[f] is the sorted array of uint64 order keys (split_hi_lo) of
    every threshold the model compares feature f against.  code(x) =
    searchsorted(table, key(x)) satisfies  x <= thr[i]  <=>  code(x) <=
    rank(thr[i])  EXACTLY in the f64 total order — and the codes are
    tiny integers, so the device upload is uint16 instead of raw keys
    (16x fewer bytes) and the selection matmul needs a single
    exactly-representable plane.  The
    serving flat-table engine passes dtype=int32 instead: it compares on
    the host, so it never needs the uint16 size cap."""
    key = order_key(hi, lo)
    out = np.zeros(hi.shape, dtype=dtype)
    for f, table in enumerate(tables):
        if len(table):
            out[:, f] = np.searchsorted(table, key[:, f],
                                        side="left").astype(dtype)
    return out


def threshold_rank_tables(trees, sf: np.ndarray, th: np.ndarray,
                          tl: np.ndarray, ftot: int):
    """Per-feature sorted threshold-key tables + per-node order keys.

    The shared first half of every rank-encoded pack: `tables[f]` holds
    the sorted uint64 order keys of all thresholds the model compares
    feature f against, `key` is the [T, M] node threshold keys and
    `real` masks the populated node slots.  matmul_host_arrays (device
    route) and serving/flatforest.compile_flat (host fast path) both
    rank their nodes against THESE tables, which is what makes the two
    routes' compares identical by construction."""
    t_cnt = len(trees)
    m = sf.shape[1]
    key = order_key(th, tl)                   # [T, M] order keys
    real = np.zeros((t_cnt, m), dtype=bool)
    for i in range(t_cnt):
        real[i, :trees[i].num_leaves - 1] = True
    tables = [np.unique(key[real & (sf == f)]) for f in range(ftot)]
    return tables, key, real


def matmul_host_arrays(trees, sf, th, tl, lc, rc, max_l, m, ftot,
                       tree_block):
    """Host-side arrays for the gather-free matmul predictor, shared by
    the batch path (models/gbdt.py _matmul_pack) and the serving forest
    (serving/forest.py) so the two packs cannot drift: one-hot feature
    selection, per-feature threshold rank tables (for rank_encode) +
    node rank codes, and per-tree path matrices.

    trees: the Tree list; sf/th/tl/lc/rc: the [T, M] padded node arrays
    (split_hi_lo threshold words); ftot: model feature width;
    tree_block: scan block multiple the tree count pads to.  Returns
    (tables, sel, thr_code, pos, neg, depth) as numpy arrays, or None
    when the pack declines (wide-feature selection matrix, uint16 code
    overflow) and the descent path should serve instead.
    """
    t_cnt = len(trees)
    # pad the tree count to the scan's block multiple; dummy trees
    # have an all-zero path and depth[0] = 0, so they argmax to leaf
    # 0 and are sliced off by the caller
    t_pad = -(-t_cnt // tree_block) * tree_block
    if ftot * t_pad * m > (1 << 26):
        # wide-feature models would make the one-hot selection
        # matrix hundreds of MB (e.g. 200k sparse features); the
        # descent path handles those instead
        return None
    sel = np.zeros((ftot, t_pad * m), dtype=np.float32)
    for i in range(t_cnt):
        for j in range(trees[i].num_leaves - 1):
            sel[sf[i, j], i * m + j] = 1.0
    tables, key, _ = threshold_rank_tables(trees, sf, th, tl, ftot)
    if max(len(t) for t in tables) >= 65535:
        return None   # uint16 codes overflow; descent path instead
    thr_code = np.zeros(t_pad * m, dtype=np.float32)
    for i in range(t_cnt):
        for j in range(trees[i].num_leaves - 1):
            thr_code[i * m + j] = np.searchsorted(
                tables[sf[i, j]], key[i, j], side="left")
    pos = np.zeros((t_pad, m, max_l), dtype=np.float32)
    neg = np.zeros((t_pad, m, max_l), dtype=np.float32)
    depth = np.full((t_pad, max_l), np.inf, dtype=np.float32)
    depth[t_cnt:, 0] = 0.0
    for i, t in enumerate(trees):
        # DFS from the root: child >= 0 is an internal node, ~child
        # is a leaf (tree.py wire format)
        stack = [(0, [])] if t.num_leaves > 1 else []
        if t.num_leaves == 1:
            depth[i, 0] = 0.0
        while stack:
            node, path = stack.pop()
            for child, sign in ((lc[i, node], 1.0),
                                (rc[i, node], -1.0)):
                cpath = path + [(node, sign)]
                if child < 0:
                    leaf = ~child
                    depth[i, leaf] = len(cpath)
                    for nd, sg in cpath:
                        (pos if sg > 0 else neg)[i, nd, leaf] = 1.0
                else:
                    stack.append((int(child), cpath))
    return tables, sel, thr_code, pos, neg, depth
