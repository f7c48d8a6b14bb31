"""`reference.py` for a job that samples: upstream's bagging and feature
streams derived from the two seeds alone, the program's bags CHECKED against
the first, its trees' features against the second, and the same comparison
read under sampling.

It imports `reference.py`'s tree replay, histograms, gains and judge, and
nothing of the program.  Upstream (LightGBM's `utils/random.h`,
`GBDT::Bagging`, `SerialTreeLearner::Train`):

  Random(seed)    a `std::mt19937(seed)`: state word 0 is the seed, word i is
                  1812433253 (w[i-1] ^ (w[i-1] >> 30)) + i mod 2^32
  NextDouble()    libstdc++'s `generate_canonical<double, 53>` over two
                  words x1, x2 of the generator: (x1 + x2 2^32) / 2^64
  Sample(n, k)    for i = 0 .. n-1, in order: row i is taken when
                  NextDouble() < (k - taken so far) / (n - i); n draws, k taken
  a bag           Random(bagging_seed).Sample(rows, int(bagging_fraction rows))
                  before tree t where t mod bagging_freq = 0, continuing ONE
                  stream; the trees until the next draw grow on it
  a tree's set    Random(feature_fraction_seed).Sample(F, int(feature_fraction
                  F)) before EVERY tree, continuing one stream

The words are numpy's `MT19937` bit generator's with its state set to the
seeding above (the same recurrence, in C), taken in chunks.  The walk is
sequential, so a bag is not rebuilt but checked: given the program's bits m,
taken_i is the exclusive cumulative sum of m, and every row has to satisfy
m_i == (draw_i < (k - taken_i) / (n - i)): one vectorised pass an epoch.  A
bag that passes IS upstream's: the walk's first wrong bit fails its own row.

Under sampling a checked tree's histograms are over the epoch's in-bag rows
(the row weight is the bag's bit), the best split any open leaf offered is
taken over the features the tree may use (with any other it DID use, so that
its gain can be stated: `feature_gap` counts such a tree), leaf values come
from the bag's sums, leaf counts are in-bag rows; the score vector is over
ALL rows, in the bag or out.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import jax.numpy as jnp

from harness import reference
from harness.reference import ROW_BLOCK, Produced

WORDS = 624             # state words of MT19937
CHUNK_ROWS = 1 << 20    # rows a step of the streams and of the bag's check


# -- upstream's streams ------------------------------------------------------
class Stream:
    """`Random(seed)`'s NextDouble stream."""

    def __init__(self, seed: int):
        key = [int(seed) & 0xFFFFFFFF]
        for i in range(1, WORDS):
            prev = key[-1]
            key.append((1812433253 * (prev ^ (prev >> 30)) + i) & 0xFFFFFFFF)
        self.words = np.random.MT19937()
        self.words.state = {"bit_generator": "MT19937", "state": {
            "key": np.asarray(key, np.uint32), "pos": WORDS}}

    def doubles(self, count: int) -> np.ndarray:
        """The next `count` NextDouble draws (count <= CHUNK_ROWS a call
        keeps the words in cache)."""
        raw = self.words.random_raw(2 * count)          # uint64, < 2^32 each
        # x1 + x2 2^32 is exact in 64 bits; the cast to double rounds once,
        # to nearest even, as the float64 sum does
        return (raw[0::2] | (raw[1::2] << np.uint64(32))).astype(
            np.float64) * 2.0 ** -64

    def sample(self, n: int, k: int) -> np.ndarray:
        """Sample(n, k) as a mask, by the walk itself (for small n)."""
        draws = self.doubles(n)
        mask = np.zeros(n, bool)
        taken = 0
        for i in range(n):
            if draws[i] < (k - taken) / (n - i):
                mask[i] = True
                taken += 1
        return mask


def bag_gap(bits: np.ndarray, n: int, k: int, stream: Stream) -> float:
    """The share of the n rows whose bit (`bits`: the bag packed as
    `np.packbits` packs it) breaks the walk's condition on the stream's next
    n draws, or the bag's count's distance from k over n if that is more."""
    bad, taken = 0, 0
    ahead = np.arange(min(CHUNK_ROWS, n), dtype=np.int64)
    for lo in range(0, n, CHUNK_ROWS):
        m = min(CHUNK_ROWS, n - lo)
        bag = np.unpackbits(bits[lo // 8:(lo + m + 7) // 8], count=m)
        before = taken + np.cumsum(bag, dtype=np.int64) - bag
        left = (n - lo) - ahead[:m]
        want = stream.doubles(m) < (k - before) / left
        bad += int(np.count_nonzero(want != bag.astype(bool)))
        taken += int(bag.sum())
    return max(bad, abs(taken - k)) / n


def feature_sets(params: dict, features: int, trees: int) -> List[np.ndarray]:
    """[trees] masks over the features: upstream's set for each tree."""
    fraction = float(params.get("feature_fraction", 1.0))
    if fraction >= 1.0:
        return [np.ones(features, bool)] * trees
    stream = Stream(int(params["feature_fraction_seed"]))
    return [stream.sample(features, int(features * fraction))
            for _ in range(trees)]


# -- the comparison ----------------------------------------------------------
def on_columns(tree: dict, cols: np.ndarray) -> dict:
    """The tree with its split features renumbered to their place in
    `cols` (sorted feature numbers that hold every feature it splits on)."""
    return dict(tree, split_feature=np.searchsorted(
        cols, np.asarray(tree["split_feature"])))


def compare(bins: np.ndarray, label: np.ndarray, params: dict,
            produced: Produced, checked: Sequence[int],
            bags: Dict[int, np.ndarray],
            control: bool = False) -> Dict[str, float]:
    """`reference.compare` under sampling, with `bag_gap` and
    `feature_gap`.  `bags[e]` is the bag the program grew the trees of epoch
    e on (trees e x bagging_freq and on), in file order, packed to bits.
    Needs the device free of the program's state."""
    trees = produced.trees
    n_trees = len(trees)
    f, n = bins.shape
    freq = int(params["bagging_freq"])
    in_bag = int(float(params["bagging_fraction"]) * n)

    numbers: Dict[str, float] = {"bag_gap": 0.0}
    stream = Stream(int(params["bagging_seed"]))
    for epoch in range(-(-n_trees // freq)):
        gap = (bag_gap(bags[epoch], n, in_bag, stream)
               if epoch in bags else 1.0)
        numbers["bag_gap"] = max(numbers["bag_gap"], gap)
    allowed = feature_sets(params, f, n_trees)
    numbers["feature_gap"] = float(sum(
        not allowed[t][np.asarray(tree["split_feature"], np.int64)].all()
        for t, tree in enumerate(trees)))

    n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
    bins_dev = jnp.pad(jnp.asarray(bins), ((0, 0), (0, n_pad - n)))
    pad1 = lambda a: jnp.asarray(np.pad(a, (0, n_pad - n)))
    sign = pad1(np.where(label > 0.5, 1.0, -1.0).astype(np.float32))

    score, upto = jnp.zeros(n_pad, jnp.float32), 0
    for t in sorted(set(checked)) + [n_trees]:
        for j in range(upto, t):
            score = reference.add_tree(
                score, bins_dev, trees[j],
                np.asarray(trees[j]["leaf_value"], np.float64))
        upto = t
        if t == n_trees:
            break
        if t // freq not in bags:
            continue                    # bag_gap has said so
        weight = pad1(np.unpackbits(bags[t // freq], count=n)
                      .astype(np.float32))
        grad, hess = reference.binary_gradients(
            score, sign, jnp.float32(params["sigmoid"]))
        exact, eighth = reference.leaf_histograms(
            bins_dev, reference.leaf_ids(bins_dev, trees[t]), grad, hess,
            weight, control)
        used = np.zeros(f, bool)
        used[np.asarray(trees[t]["split_feature"], np.int64)] = True
        cols = np.flatnonzero(allowed[t] | used)
        got = reference.check_tree(
            on_columns(trees[t], cols), exact[:, cols],
            eighth[:, cols] if control else None, params,
            params["learning_rate"])
        for k, v in got.items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    total = np.asarray(score)[:n]
    numbers["score_gap"] = float(np.abs(produced.scores - total).max()
                                 / max(np.abs(total).max(), 1e-30))
    numbers["trees_missing"] = float(produced.trees_asked - n_trees)
    return numbers
