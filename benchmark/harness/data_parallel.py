"""`data.make_rows` for a host's share of the rows: the same arrays, the
tiles written by a pool of threads.

At 273M rows the one generator's tile loop (a strided write of 39 x 1M
bytes and a 1M-row gather a tile, first touch of fresh pages) takes four
times what it takes at 68M; the block of distinct rows, its binning and
the label model are `data.py`'s own, made once.  `tests/test_data4.py`
holds the two generators equal, byte for byte.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness.data import Rows, _columns, equal_population_bounds


def make_rows(data: dict, num_data: int, max_bin: int, seed: int,
              threads: int = 0) -> Rows:
    rng = np.random.default_rng([int(data["population_seed"]), 0x6C67626D])
    block = int(min(data["block_rows"], num_data))
    bounds, rows_of_bins = [], []
    for group in data["columns"]:
        for col in _columns(rng, group, block):
            b = equal_population_bounds(col, max_bin)
            bounds.append(b)
            rows_of_bins.append(
                np.searchsorted(b, col, side="left").astype(np.uint8))
    block_bins = np.stack(rows_of_bins)
    lab = data["label"]

    def rank(col: int) -> np.ndarray:
        r = block_bins[col].astype(np.float32)
        return r / max(float(r.max()), 1.0) - 0.5

    logit = np.full(block, lab["bias"], np.float32)
    for col, w in lab["weights"]:
        logit += np.float32(w) * rank(col)
    for a, b, w in lab["pairs"]:
        logit += np.float32(w) * rank(a) * rank(b)
    p_block = (1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    whole, rest = divmod(num_data, block)
    u = rng.random(num_data, dtype=np.float32)
    label = np.empty(num_data, np.float32)
    bins = np.empty((block_bins.shape[0], num_data), np.uint8)

    order = np.random.default_rng([int(seed), 0x6F726472])
    rows_in = order.permutation(block)
    tiles = order.permutation(whole)
    shuffled = block_bins[:, rows_in]
    p_shuffled = p_block[rows_in]

    def write(at: int) -> None:
        lo, tile = at * block, int(tiles[at])
        bins[:, lo:lo + block] = shuffled
        label[lo:lo + block] = (u[tile * block:(tile + 1) * block][rows_in]
                                < p_shuffled)

    with ThreadPoolExecutor(threads or min(os.cpu_count() or 1, 16)) as pool:
        list(pool.map(write, range(whole)))
    if rest:
        last = order.permutation(rest)
        bins[:, whole * block:] = block_bins[:, :rest][:, last]
        label[whole * block:] = u[whole * block:][last] < p_block[:rest][last]
    return Rows(bins=bins, upper_bounds=bounds, label=label)
