"""The data generator for a ranking configuration: its `data` block ->
binned rows in whole queries.

In the pattern of `data.py`.  The POPULATION is the configuration's: from
its `population_seed` ONE block of `block_queries` distinct queries, each
with its length and its documents' columns, every column binned into at
most `max_bin` equal-population bins (`data.equal_population_bounds`),
the block tiled to `num_queries` queries (whole tiles, then the block's
first queries as a last, partial tile), and every document's label drawn
afresh from its block logit, so that no two copies of a query share a
gradient history.  `--seed` gives the ORDER OF WHOLE QUERIES: one
permutation of the block's queries (the same in every whole tile), one of
the tiles among themselves, one of the partial tile's queries.  The rows
of a query stay contiguous and keep their order: lambdarank breaks score
ties by position in the query.  Every seed so trains on the same multiset
of queries.

Query lengths: `lmax` x Beta(a, b), rounded up, with a and b from the
stated mean and coefficient of variation (a law on [1, lmax] that is dense
at the cap, as a collection whose candidate lists are cut at a fixed depth
is), the shortest set to `min` and the longest to `max`, then single
documents added or taken so that the tiled total is `num_data` EXACTLY.

Column kinds (a list of groups in the configuration file):
  query       one lognormal(mean, sigma) draw a QUERY, the same for all its
              documents (query length, term counts, idf sums)
  doc_score   normal per document around a per-query centre: `within` is
              the document's share of the variance (BM25-like match scores)
  counter     `data.py`'s: floor(lognormal) with a zero rate, per document
              (term frequencies, link counts)

Labels 0..4 (ordinal): label = how many of the four `thresholds` the
document's logit plus fresh logistic noise passes; the logit is
`data.py`'s model over bin ranks.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from harness.data import _columns, equal_population_bounds


@dataclasses.dataclass
class RankedRows:
    bins: np.ndarray                 # [F, N] uint8, feature-major
    upper_bounds: List[np.ndarray]   # per feature, f64, last is +inf
    label: np.ndarray                # [N] float32 in {0, 1, 2, 3, 4}
    query_boundaries: np.ndarray     # [Q + 1] int32, file order


def _spread(lengths: np.ndarray, queries: np.ndarray, docs: int, lo: int,
            hi: int) -> None:
    """`docs` single documents added to (taken from, where negative) the
    `queries`, one each in turn, every length kept inside (lo, hi)."""
    step = 1 if docs > 0 else -1
    left = abs(docs)
    while left:
        new = lengths[queries] + step
        room = queries[(new > lo) & (new < hi)][:left]
        if not len(room):
            raise ValueError("no room for %d more documents" % (step * left))
        lengths[room] += step
        left -= len(room)


def block_lengths(law: dict, block_queries: int, num_queries: int,
                  num_data: int, rng: np.random.Generator) -> np.ndarray:
    """[block_queries] int64 lengths whose tiling to `num_queries` queries
    holds `num_data` documents exactly."""
    lo, hi = int(law["min"]), int(law["max"])
    m = float(law["mean"]) / hi
    ab = m * (1.0 - m) / (float(law["cv"]) * m) ** 2 - 1.0
    lengths = np.clip(np.ceil(hi * rng.beta(m * ab, (1.0 - m) * ab,
                                            block_queries)), lo, hi)
    lengths = lengths.astype(np.int64)
    shortest, longest = int(np.argmin(lengths)), int(np.argmax(lengths))
    lengths[shortest], lengths[longest] = lo, hi
    whole, rest = divmod(num_queries, block_queries)
    # a document more in one of the block's first `rest` queries counts
    # whole + 1 times in the tiling, in one of the others `whole` times;
    # the two are coprime, so every gap is a sum of such steps
    free = rng.permutation(block_queries)
    free = free[(free != shortest) & (free != longest)]
    gap = num_data - int(lengths.sum() * whole + lengths[:rest].sum())
    first = ((gap + whole // 2) % whole - whole // 2) if rest else 0
    others, left = divmod(gap - first * (whole + 1), whole)
    if left:
        raise ValueError("%d queries in whole tiles of %d cannot hold %d "
                         "documents" % (num_queries, block_queries, num_data))
    _spread(lengths, free[free < rest], first, lo, hi)
    _spread(lengths, free[free >= rest], others, lo, hi)
    return lengths


def _ranked_columns(rng: np.random.Generator, group: dict,
                    query_of: np.ndarray, queries: int):
    """Yield the group's columns one at a time, each [documents] float64."""
    kind = group["kind"]
    docs = len(query_of)
    if kind == "counter":
        yield from _columns(rng, group, docs)
        return
    for _ in range(int(group["columns"])):
        if kind == "query":
            yield rng.lognormal(group["mean"], group["sigma"],
                                queries)[query_of]
        elif kind == "doc_score":
            w = float(group["within"])
            yield (np.sqrt(1.0 - w) * rng.standard_normal(queries)[query_of]
                   + np.sqrt(w) * rng.standard_normal(docs))
        else:
            raise ValueError("unknown column kind %r" % kind)


def make_ranked_rows(data: dict, num_data: int, num_queries: int,
                     max_bin: int, seed: int) -> RankedRows:
    rng = np.random.default_rng([int(data["population_seed"]), 0x72616E6B])
    block_q = int(min(data["block_queries"], num_queries))
    lengths = block_lengths(data["query_length"], block_q, num_queries,
                            num_data, rng)
    starts = np.concatenate([[0], np.cumsum(lengths)])
    block = int(starts[-1])
    query_of = np.repeat(np.arange(block_q), lengths)
    bounds, rows_of_bins = [], []
    for group in data["columns"]:
        for col in _ranked_columns(rng, group, query_of, block_q):
            b = equal_population_bounds(col, max_bin)
            bounds.append(b)
            rows_of_bins.append(
                np.searchsorted(b, col, side="left").astype(np.uint8))
    block_bins = np.stack(rows_of_bins)
    lab = data["label"]

    def rank(col: int) -> np.ndarray:
        r = block_bins[col].astype(np.float32)
        return r / max(float(r.max()), 1.0) - 0.5

    logit = np.zeros(block, np.float32)
    for col, w in lab["weights"]:
        logit += np.float32(w) * rank(col)
    for a, b, w in lab["pairs"]:
        logit += np.float32(w) * rank(a) * rank(b)
    # P(label >= k) = sigmoid(logit - threshold k): one uniform a document
    cuts = np.asarray(lab["thresholds"], np.float32)[:, None]
    p_block = (1.0 / (1.0 + np.exp(cuts - logit[None, :]))).astype(np.float32)

    def labels(u: np.ndarray, p: np.ndarray) -> np.ndarray:
        return (u[None, :] < p).sum(0).astype(np.float32)

    whole, rest = divmod(num_queries, block_q)
    u = rng.random(num_data, dtype=np.float32)
    label = np.empty(num_data, np.float32)
    bins = np.empty((block_bins.shape[0], num_data), np.uint8)

    def docs_of(queries: np.ndarray) -> np.ndarray:
        """The block's document positions of `queries`, in that order."""
        n = lengths[queries]
        first = np.repeat(starts[queries] - np.concatenate(
            [[0], np.cumsum(n)[:-1]]), n)
        return first + np.arange(int(n.sum()))

    order = np.random.default_rng([int(seed), 0x6F726472])
    queries_in = order.permutation(block_q)
    tiles = order.permutation(whole)
    rows_in = docs_of(queries_in)
    shuffled = block_bins[:, rows_in]
    p_shuffled = p_block[:, rows_in]
    for at, tile in enumerate(tiles):
        lo = at * block
        bins[:, lo:lo + block] = shuffled
        label[lo:lo + block] = labels(
            u[tile * block:(tile + 1) * block][rows_in], p_shuffled)
    all_lengths = [np.tile(lengths[queries_in], whole)]
    if rest:
        last_q = order.permutation(rest)
        last = docs_of(last_q)
        bins[:, whole * block:] = block_bins[:, last]
        label[whole * block:] = labels(u[whole * block:][last],
                                       p_block[:, last])
        all_lengths.append(lengths[last_q])
    boundaries = np.concatenate([[0], np.cumsum(np.concatenate(all_lengths))])
    return RankedRows(bins=bins, upper_bounds=bounds, label=label,
                      query_boundaries=boundaries.astype(np.int32))
