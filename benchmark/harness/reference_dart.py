"""`reference.py` for dropout boosting (DART: Rashmi and Gilad-Bachrach,
AISTATS 2015, as LightGBM's `src/boosting/dart.hpp` of the 2016 snapshot has
it): upstream's drop lists derived from `drop_seed` alone, the program's
CHECKED against them, every tree's weight at every iteration derived from the
lists, and the same comparison read under dropping.

It imports `reference.py`'s tree replay, histograms, gains and judge (and
`reference_bagged.py`'s stream, which is upstream's `Random`), and nothing of
the program.  Upstream, at iteration t (trees 0 .. t-1 exist):

  the lottery     Random(drop_seed), ONE stream for the job: t NextDouble
                  draws, tree j is dropped where draw_j < drop_rate; where
                  none is and t > 0, one tree is forced: Sample(t, 1), t more
                  draws.  (The snapshot has no skip_drop, max_drop or
                  uniform_drop: EVERY iteration past the first drops.)
  k               the number dropped
  the gradients   are taken at the scores WITHOUT the dropped trees
  the new tree    is shrunk by 1 / (1 + k), in learning_rate's place
  normalise       every dropped tree is scaled by k / (1 + k) for good

So a tree's weight is a product over the job: born with 1 / (1 + k_t), times
k_s / (1 + k_s) at every later iteration s that dropped it.  A run DELIVERS
its trees as they stand at its end: tree j's leaf values are -G / (H + l2)
times its FINAL weight W_j(end), and its values at the start of iteration t
are the delivered ones times W_j(t) / W_j(end).  All of that follows from the
lists alone.

For a CHECKED tree t the scores its gradients must have seen are the sum over
the earlier trees NOT dropped at t of their replayed leaf values at their
weight then; gradients, histograms with exact products, every open leaf's
best split and the leaf values -G / (H + l2) x W_t(end) are then
`reference.py`'s.  `score_gap` is over the FINAL scores against the sum of the
delivered trees by replay; `drop_gap` counts the iterations whose drop list, as
the program reports it, differs from the stream's.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from harness import reference
from harness.reference import ROW_BLOCK, Produced
from harness.reference_bagged import Stream


@jax.jit
def _add_leaf_values(score, leaf, table):
    return score + table[leaf.astype(jnp.int32)]


def drop_lists(params: dict, iterations: int) -> List[List[int]]:
    """Upstream's drop list of every iteration 0 .. iterations-1."""
    rate = float(params["drop_rate"])
    stream = Stream(int(params["drop_seed"]))
    lists: List[List[int]] = []
    for t in range(iterations):
        dropped: List[int] = []
        if t > 0:
            if rate > 1e-15:
                dropped = [int(j) for j in
                           np.flatnonzero(stream.doubles(t) < rate)]
            if not dropped:
                dropped = [int(j) for j in
                           np.flatnonzero(stream.sample(t, 1))]
        lists.append(dropped)
    return lists


def weights(lists: Sequence[Sequence[int]]) -> np.ndarray:
    """W[t, j]: tree j's weight at the START of iteration t (row
    len(lists): at the job's end), float64; 0 where j is not born yet."""
    n = len(lists)
    w = np.zeros((n + 1, n), np.float64)
    for t, dropped in enumerate(lists):
        k = len(dropped)
        w[t + 1] = w[t]
        w[t + 1, list(dropped)] *= k / (1.0 + k)
        w[t + 1, t] = 1.0 / (1.0 + k)
    return w


def compare(bins: np.ndarray, label: np.ndarray, params: dict,
            produced: Produced, checked: Sequence[int],
            reported: Sequence[Sequence[int]],
            control: bool = False) -> Dict[str, float]:
    """`reference.compare` under dropping, with `drop_gap`.  `reported[t]`
    is the drop list the program says iteration t used.  Needs the device
    free of the program's state."""
    trees = produced.trees
    n_trees = len(trees)
    f, n = bins.shape
    lists = drop_lists(params, max(n_trees, len(reported)))
    numbers: Dict[str, float] = {"drop_gap": float(sum(
        t >= len(reported) or sorted(int(j) for j in reported[t]) != lists[t]
        for t in range(len(lists))))}
    lists = lists[:n_trees]
    w = weights(lists)
    delivered = [np.asarray(t["leaf_value"], np.float64) for t in trees]

    n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
    bins_dev = jnp.pad(jnp.asarray(bins), ((0, 0), (0, n_pad - n)))
    pad1 = lambda a: jnp.asarray(np.pad(a, (0, n_pad - n)))
    sign = pad1(np.where(label > 0.5, 1.0, -1.0).astype(np.float32))
    weight = pad1(np.ones(n, np.float32))

    # every tree's leaf assignment, replayed ONCE (a byte a row and tree on
    # the device, which is free of the program's state): the four sums below
    # each add 32 to 48 trees
    ids = [reference.leaf_ids(bins_dev, tree).astype(jnp.uint8)
           for tree in trees]

    def scores_of(values: Sequence) -> jnp.ndarray:
        """The sum of the trees' leaf values over their replayed leaves,
        tree by tree in float32; None leaves a tree out."""
        score = jnp.zeros(n_pad, jnp.float32)
        for j, v in enumerate(values):
            if v is not None:
                table = np.zeros(reference.LEAF_PAD, np.float32)
                table[:len(v)] = v
                score = _add_leaf_values(score, ids[j], jnp.asarray(table))
        return score

    for t in sorted(set(checked)):
        if not 0 <= t < n_trees:
            continue
        seen = [None if j in lists[t] else delivered[j] * (w[t, j] / w[-1, j])
                for j in range(t)]
        grad, hess = reference.binary_gradients(
            scores_of(seen), sign, jnp.float32(params["sigmoid"]))
        exact, eighth = reference.leaf_histograms(
            bins_dev, ids[t].astype(jnp.int32), grad, hess, weight, control)
        for k, v in reference.check_tree(trees[t], exact, eighth, params,
                                         w[-1, t]).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    total = np.asarray(scores_of(delivered))[:n]
    numbers["score_gap"] = float(np.abs(produced.scores - total).max()
                                 / max(np.abs(total).max(), 1e-30))
    numbers["trees_missing"] = float(produced.trees_asked - n_trees)
    return numbers
