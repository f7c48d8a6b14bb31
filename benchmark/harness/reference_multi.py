"""`reference.py` for a class-wise job (ten-class softmax boosting, upstream's
`objective=multiclass`: `src/objective/multiclass_objective.hpp`, one tree a
class an iteration, `gbdt.cpp:177-197`).

It imports `reference.py`'s tree replay, histograms, gains and `judge`, and
nothing of the program.  Tree t belongs to iteration t // K and class
t mod K.  An iteration's gradients are computed ONCE, from the scores before
it (the sum of every earlier iteration's trees, class by class, by replay),
and all K of its trees are grown on them: with p = softmax over the K
classes' scores of a row,

  g_k = p_k - 1{y = k},   h_k = 2 p_k (1 - p_k)

(SURVEY.md section 2.4), in plain `jax.numpy` float32 under "highest"
matmul precision.  For a CHECKED tree the histograms, every open leaf's
best split and the leaf values -G / (H + l2) x learning rate are then
`reference.py`'s, over the tree's own class's gradients.  `score_gap` is
over all K rows of the FINAL scores against the delivered trees' sum class
by class; `trees_missing` as ever.  The control rounds the gradients to
float8 before the histograms (`reference.leaf_histograms`).
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from harness import reference
from harness.reference import ROW_BLOCK, Produced


@jax.jit
def softmax_gradients(scores, label):
    """[K, N] float32 scores, [N] int32 classes (-1: a padded row, of no
    class) -> the [K, N] gradients and hessians."""
    p = jax.nn.softmax(scores, axis=0)
    onehot = (label[None, :] == jnp.arange(scores.shape[0])[:, None])
    return p - onehot.astype(jnp.float32), 2.0 * p * (1.0 - p)


@jax.jit
def _add_leaf_values(score, leaf, table):
    return score + table[leaf.astype(jnp.int32)]


def compare(bins: np.ndarray, label: np.ndarray, params: dict,
            produced: Produced, checked: Sequence[int],
            control: bool = False) -> Dict[str, float]:
    """`reference.compare` for K classes; `produced.scores` is [K, N].
    Needs the device free of the program's state."""
    trees = produced.trees
    k = int(params["num_class"])
    f, n = bins.shape
    n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
    bins_dev = jnp.pad(jnp.asarray(bins), ((0, 0), (0, n_pad - n)))
    lab = jnp.asarray(np.pad(label.astype(np.int32), (0, n_pad - n),
                             constant_values=-1))
    weight = jnp.asarray(np.pad(np.ones(n, np.float32), (0, n_pad - n)))
    # every tree's leaf assignment, replayed ONCE (a byte a row and tree)
    ids = [reference.leaf_ids(bins_dev, t).astype(jnp.uint8) for t in trees]
    by_iter: Dict[int, list] = {}
    for t in sorted(set(checked)):
        if 0 <= t < len(trees):
            by_iter.setdefault(t // k, []).append(t)

    numbers: Dict[str, float] = {}
    scores = [jnp.zeros(n_pad, jnp.float32) for _ in range(k)]
    with jax.default_matmul_precision("highest"):
        for it in range(-(-len(trees) // k)):
            if it in by_iter:
                grad, hess = softmax_gradients(jnp.stack(scores), lab)
                for t in by_iter[it]:
                    exact, eighth = reference.leaf_histograms(
                        bins_dev, ids[t].astype(jnp.int32), grad[t % k],
                        hess[t % k], weight, control)
                    for name, v in reference.check_tree(
                            trees[t], exact, eighth, params,
                            params["learning_rate"]).items():
                        numbers[name] = max(numbers.get(name, 0.0), v)
                del grad, hess
            for t in range(it * k, min((it + 1) * k, len(trees))):
                values = np.asarray(trees[t]["leaf_value"], np.float64)
                table = np.zeros(reference.LEAF_PAD, np.float32)
                table[:len(values)] = values
                scores[t % k] = _add_leaf_values(scores[t % k], ids[t],
                                                 jnp.asarray(table))
    total = np.asarray(jnp.stack(scores))[:, :n]
    numbers["score_gap"] = float(np.abs(produced.scores - total).max()
                                 / max(np.abs(total).max(), 1e-30))
    numbers["trees_missing"] = float(produced.trees_asked - len(trees))
    return numbers
