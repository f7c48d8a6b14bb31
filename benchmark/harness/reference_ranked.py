"""`reference.py` for a ranking job: lambdarank's lambdas and hessians from
the published formula, and the same comparison with them in the place of
the binary gradients.

It imports `reference.py`'s tree replay, histograms, gains and judge, and
nothing of the program.  The gradients (Burges, "From RankNet to LambdaRank
to LambdaMART"; upstream's 2016 `rank_objective.hpp`), for a query of
documents with score s, grade l, gain g = label_gain[l] and position i:

  rank_i    = #{j: s_j > s_i} + #{j < i: s_j = s_i}       (ties by position)
  disc_i    = 1 / log2(2 + rank_i)
  for every pair (h, l) of the query with grade_h > grade_l, ds = s_h - s_l:
    delta   = (g_h - g_l) |disc_h - disc_l| / maxDCG@max_position
    delta  /= 0.01 + |ds|          where the query's best and worst scores differ
    p       = 2 / (1 + exp(2 sigma ds))
    lambda_h -= p delta;  lambda_l += p delta
    hess_h  += p (2 - p) 2 delta;  hess_l += the same
  each document's sums times its weight.

Plain `jax.numpy` float32 under `jax.default_matmul_precision("highest")`.
The rank is COUNTED from the pairs (no sort), and the queries are taken in
this file's own blocks: grouped by length rounded up to LENGTH_STEP, some
PAIR_CELLS pair cells a step of a `lax.map`.  A query's documents are
contiguous rows in file order; nothing else is assumed of the layout.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from harness import reference
from harness.reference import ROW_BLOCK, Produced

LENGTH_STEP = 64        # a query is padded to the next multiple
PAIR_CELLS = 1 << 22    # [queries, L, L] cells a step


def inverse_max_dcg(label: np.ndarray, boundaries: np.ndarray,
                    label_gain: np.ndarray, k: int) -> np.ndarray:
    """[Q] 1 / (the best DCG of the query's first k places), 0 for a query
    with no gain to win; float64 on the host."""
    grades = len(label_gain)
    counts = np.stack([np.add.reduceat((label == g).astype(np.int64),
                                       boundaries[:-1])
                       for g in range(grades)], axis=1)        # [Q, grades]
    counts[np.diff(boundaries) == 0] = 0
    disc_sum = np.concatenate([[0.0], np.cumsum(
        1.0 / np.log2(2.0 + np.arange(k)))])
    best = np.zeros(len(boundaries) - 1)
    placed = np.zeros(len(boundaries) - 1, np.int64)
    for g in range(grades - 1, -1, -1):         # the highest grades first
        upto = np.minimum(placed + counts[:, g], k)
        best += label_gain[g] * (disc_sum[upto] - disc_sum[placed])
        placed = upto
    return np.where(best > 0.0, 1.0 / np.maximum(best, 1e-300), 0.0)


@functools.partial(jax.jit, static_argnames=("sigma",))
def _pair_pass(score, rows, valid, grade, gain, inv, weight, sigma):
    """-> ([chunks, q, L] lambdas, hessians) of queries laid out
    [chunks, q, L]: `rows` their documents' places in `score`."""
    position = jnp.arange(rows.shape[-1])

    def chunk(xs):
        r, ok, l, g, iv, w = xs
        s = score[r]                                            # [q, L]
        si, sj = s[:, :, None], s[:, None, :]
        both = ok[:, :, None] & ok[:, None, :]
        before = (sj > si) | ((sj == si) & (position[None, None, :]
                                            < position[None, :, None]))
        rank = (before & both).sum(-1)
        disc = jnp.where(ok, 1.0 / jnp.log2(2.0 + rank.astype(jnp.float32)),
                         0.0)
        best = jnp.max(jnp.where(ok, s, -jnp.inf), -1)
        worst = jnp.min(jnp.where(ok, s, jnp.inf), -1)
        ds = si - sj
        delta = ((g[:, :, None] - g[:, None, :])
                 * jnp.abs(disc[:, :, None] - disc[:, None, :])
                 * iv[:, None, None])
        delta = jnp.where((best != worst)[:, None, None],
                          delta / (0.01 + jnp.abs(ds)), delta)
        p = 2.0 / (1.0 + jnp.exp(2.0 * sigma * ds))
        higher = both & (l[:, :, None] > l[:, None, :])
        lam = jnp.where(higher, p * delta, 0.0)
        hes = jnp.where(higher, p * (2.0 - p) * 2.0 * delta, 0.0)
        # the pair's higher document is the middle axis, its lower the last
        return ((lam.sum(1) - lam.sum(2)) * w * ok,
                (hes.sum(1) + hes.sum(2)) * w * ok)

    return jax.lax.map(chunk, (rows, valid, grade, gain, inv, weight))


class Ranker:
    """The queries of a dataset in this file's blocks, on the device."""

    def __init__(self, label: np.ndarray, boundaries: np.ndarray,
                 params: dict, n_pad: int,
                 weights: Optional[np.ndarray] = None):
        label_gain = np.asarray(
            [float(x) for x in str(params["label_gain"]).split(",")])
        self.sigma = float(params["sigmoid"])
        boundaries = np.asarray(boundaries, np.int64)
        lengths = np.diff(boundaries)
        inv = inverse_max_dcg(label.astype(np.int64), boundaries, label_gain,
                              int(params["max_position"]))
        weights = (np.ones(len(label), np.float32) if weights is None
                   else np.asarray(weights, np.float32))
        padded = -(-lengths // LENGTH_STEP) * LENGTH_STEP
        self.groups: List[tuple] = []
        slot = np.zeros(n_pad, np.int64)
        cells = 0
        for width in np.unique(padded[lengths > 0]):
            qs = np.flatnonzero((padded == width) & (lengths > 0))
            per = max(1, PAIR_CELLS // int(width * width))
            chunks = -(-len(qs) // per)
            at = np.arange(width)
            rows = boundaries[qs, None] + np.minimum(at, lengths[qs, None] - 1)
            valid = at[None, :] < lengths[qs, None]
            slot[rows[valid]] = cells + np.flatnonzero(valid.reshape(-1))

            def laid(a, fill=0):
                """[queries, ...] -> [chunks, per, ...], the tail filled."""
                tail = chunks * per - len(qs)
                a = np.concatenate([a, np.full((tail,) + a.shape[1:], fill,
                                               a.dtype)])
                return jnp.asarray(a.reshape((chunks, per) + a.shape[1:]))

            grade = label[rows].astype(np.int32)
            self.groups.append((
                laid(rows.astype(np.int32)), laid(valid, False), laid(grade),
                laid(label_gain[grade].astype(np.float32)),
                laid(inv[qs].astype(np.float32)), laid(weights[rows])))
            cells += chunks * per * int(width)
        # rows past the data read one more, empty cell
        slot[len(label):] = cells
        self.slot = jnp.asarray(slot.astype(np.int32))

    def gradients(self, score):
        """[n_pad] lambdas and hessians at `score`."""
        with jax.default_matmul_precision("highest"):
            parts = [_pair_pass(score, *g, sigma=self.sigma)
                     for g in self.groups]
            zero = jnp.zeros(1, jnp.float32)
            lam = jnp.concatenate([p[0].reshape(-1) for p in parts] + [zero])
            hes = jnp.concatenate([p[1].reshape(-1) for p in parts] + [zero])
            return lam[self.slot], hes[self.slot]


FLOAT8_TOP = 128.0      # e4m3 holds up to 240; the largest value goes here


def scaled_float8(bins_dev, leaf, grad, hess, weight) -> np.ndarray:
    """The control's [L, F, B, 2] histograms.  Lambdas span more powers of
    two than float8 holds (2^-9 to 240), so `reference.leaf_histograms`'s
    float8 rounding is applied to each of the two arrays on a power-of-two
    scale that puts its largest value at float8's top, as a float8
    pipeline scales a tensor; the sums are scaled back."""
    def scale(a):
        top = float(jnp.abs(a).max())
        return 2.0 ** np.floor(np.log2(FLOAT8_TOP / top)) if top > 0 else 1.0
    sg, sh = scale(grad), scale(hess)
    _, eighth = reference.leaf_histograms(bins_dev, leaf, grad * sg,
                                          hess * sh, weight, True)
    return eighth / np.asarray([sg, sh])


def compare(bins: np.ndarray, label: np.ndarray, boundaries: np.ndarray,
            params: dict, produced: Produced, checked: Sequence[int],
            control: bool = False,
            weights: Optional[np.ndarray] = None) -> Dict[str, float]:
    """`reference.compare` with lambdarank's gradients: all numbers of one
    run; `checked` are the trees whose growth is recomputed.  Needs the
    device free of the program's state."""
    trees = produced.trees
    n_trees = len(trees)
    f, n = bins.shape
    n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
    bins_dev = jnp.pad(jnp.asarray(bins), ((0, 0), (0, n_pad - n)))
    in_data = jnp.asarray(np.pad(np.ones(n, np.float32), (0, n_pad - n)))
    ranker = Ranker(label, boundaries, params, n_pad, weights)

    numbers: Dict[str, float] = {}
    score, upto = jnp.zeros(n_pad, jnp.float32), 0
    for t in sorted(set(checked)) + [n_trees]:
        for j in range(upto, t):
            score = reference.add_tree(
                score, bins_dev, trees[j],
                np.asarray(trees[j]["leaf_value"], np.float64))
        upto = t
        if t == n_trees:
            break
        grad, hess = ranker.gradients(score)
        leaf = reference.leaf_ids(bins_dev, trees[t])
        exact, eighth = reference.leaf_histograms(bins_dev, leaf, grad, hess,
                                                  in_data, False)
        if control:
            eighth = scaled_float8(bins_dev, leaf, grad, hess, in_data)
        for k, v in reference.check_tree(trees[t], exact, eighth, params,
                                         params["learning_rate"]).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    total = np.asarray(score)[:n]
    numbers["score_gap"] = float(np.abs(produced.scores - total).max()
                                 / max(np.abs(total).max(), 1e-30))
    numbers["trees_missing"] = float(produced.trees_asked - n_trees)
    return numbers
