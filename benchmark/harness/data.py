"""The one data generator: a configuration's `data` block -> binned rows.

The POPULATION is the configuration's: from its `population_seed` ONE block
of distinct rows, each column binned into at most `max_bin`
equal-population bins (its own binning: the program's `find_bins` is ingest
code, not the path under test, and a yardstick that a later PR could
change), the binned [F, block] matrix tiled to `num_data` rows, and every
row's label drawn from its block logit plus fresh noise, so that no two
rows share a gradient history.  `--seed` gives the ORDER: a permutation of
the block's rows, of the whole tiles among themselves and of the last,
partial tile.  Every seed so trains on the same multiset of rows in another
order: with rows drawn afresh from the seed, 16-tree windows of cell 1 read
3.44 to 3.57 s/tree by the seed and 0.002% apart on one seed (PR 24, call
4), because other rows grow other trees.  The program receives only the
arrays.

Column kinds (a list of groups in the configuration file):
  counter      floor(lognormal(mean, sigma)) with a zero rate  (Criteo I1-I13)
  categorical  integer codes, Zipf(a) tail over `cardinality`  (Criteo C1-C26)
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Rows:
    bins: np.ndarray            # [F, N] uint8, feature-major
    upper_bounds: List[np.ndarray]   # per feature, f64, last is +inf
    label: np.ndarray           # [N] float32 in {0, 1}


def _columns(rng: np.random.Generator, group: dict, rows: int):
    """Yield the group's columns one at a time, each [rows] float64."""
    kind = group["kind"]
    for _ in range(int(group["columns"])):
        if kind == "counter":
            x = np.floor(rng.lognormal(group["mean"], group["sigma"], rows))
            x[rng.random(rows) < group["zero_rate"]] = 0.0
        elif kind == "categorical":
            # discrete Pareto: P(code > c) = c ** -(a - 1), a Zipf(a) tail
            u = 1.0 - rng.random(rows)
            x = np.floor(u ** (-1.0 / (group["zipf_a"] - 1.0)))
            x = np.minimum(x, float(group["cardinality"]))
        else:
            raise ValueError("unknown column kind %r" % kind)
        yield x


def equal_population_bounds(col: np.ndarray, max_bin: int) -> np.ndarray:
    """Upper bounds of at most `max_bin` bins.  A value that alone holds a
    bin's share of the rows gets a bin of its own; the other values share
    the remaining bins in about equal counts.  A bound sits midway between
    two distinct neighbouring values, and the last is +inf.  Value v falls
    in the first bin whose bound is >= v."""
    distinct, counts = np.unique(col, return_counts=True)
    last = len(distinct) - 1
    if len(distinct) <= max_bin:
        cut = np.arange(last)
    else:
        big = counts >= counts.sum() / max_bin
        # a big value is cut off from both neighbours
        around = np.flatnonzero(big)
        rest = np.where(big, 0, counts)
        cum = np.cumsum(rest)
        spare = max_bin - int(big.sum())
        want = cum[-1] * np.arange(1, spare) / spare
        cut = np.concatenate([around, around - 1, np.searchsorted(cum, want)])
        cut = np.unique(cut[(cut >= 0) & (cut < last)])[:max_bin - 1]
    mids = (distinct[cut] + distinct[cut + 1]) / 2.0
    return np.append(mids, np.inf)


def make_rows(data: dict, num_data: int, max_bin: int, seed: int) -> Rows:
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
    # the label model: a few columns enter through their bin RANK, so that
    # counters, categoricals and dense columns carry signal whatever their
    # scale
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
    # P(y=1) = sigmoid(block logit): fresh noise for every row of every tile
    u = rng.random(num_data, dtype=np.float32)
    label = np.empty(num_data, np.float32)
    bins = np.empty((block_bins.shape[0], num_data), np.uint8)

    order = np.random.default_rng([int(seed), 0x6F726472])
    rows_in = order.permutation(block)
    tiles = order.permutation(whole)
    shuffled = block_bins[:, rows_in]
    p_shuffled = p_block[rows_in]
    for at, tile in enumerate(tiles):
        lo = at * block
        bins[:, lo:lo + block] = shuffled
        label[lo:lo + block] = (u[tile * block:(tile + 1) * block][rows_in]
                                < p_shuffled)
    if rest:
        last = order.permutation(rest)
        bins[:, whole * block:] = block_bins[:, :rest][:, last]
        label[whole * block:] = u[whole * block:][last] < p_block[:rest][last]
    return Rows(bins=bins, upper_bounds=bounds, label=label)
