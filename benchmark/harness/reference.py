"""The plain reference for boosted-tree training, and the comparison that
decides `correct`.

It imports nothing of the program.  It is given the rows the benchmark
made (bins, labels), the configuration's parameters, and what the timed
path PRODUCED: the trees and the final score vector.  From those it
recomputes, in float32 with exact products and float64 on the host:

  * every tree's leaf assignment, by replaying its splits in order;
  * the score vector: the sum of all trees' leaf values (`score_gap`);
  * for each CHECKED tree: the gradients from the scores that tree was
    grown on, the per-leaf histograms, from them every node's histogram,
    and then, split by split, the best gain any leaf then open offered
    against the gain of the split the program chose (`gain_loss`, the
    shortfalls summed over the tree's splits as a share of the summed
    bests; `gain_gap`, the widest single shortfall), the leaf values
    (`leaf_update_gap`, the error of the tree's score update as a norm over
    the rows; `leaf_value_gap`, the widest single leaf's) and the leaf
    counts (`leaf_count_gap`).

The control is this reference in the program's place one precision down:
gradients rounded to float8 (e4m3) before the histograms, the split each
open leaf would then choose, and that choice's true gain.  It is computed
only where `compare(control=True)` asks (the tests and `chip_fault.py`),
never in a run of the benchmark.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

ROW_BLOCK = 8192        # rows per histogram product
LEAF_PAD = 64           # leaf one-hot width (num_leaves <= 64)
BIN_PAD = 256           # bin one-hot width (uint8 bins)


@dataclasses.dataclass
class Produced:
    """What the timed path produced, in plain arrays."""
    trees: List[dict]           # split_feature, threshold_bin, left_child,
                                # right_child, leaf_value, leaf_count
    scores: np.ndarray          # [N] float32, file order
    trees_asked: int            # trees the run asked for, warm-up included


# -- leaf assignment and scores ------------------------------------------
def split_sources(tree: dict) -> np.ndarray:
    """Leaf-wise growth numbers node k by the step that made it; its left
    child keeps the split leaf's id and its right child is leaf k + 1.  So
    the leaf that step k split is the one reached by going left from node
    k until a leaf."""
    left = np.asarray(tree["left_child"])
    src = np.zeros(len(left), np.int32)
    for k in range(len(left)):
        node = k
        while node >= 0:
            node = int(left[node])
        src[k] = ~node
    return src


@jax.jit
def _leaf_ids(bins, feat, thr, src, n_splits):
    def body(k, leaf):
        row = jax.lax.dynamic_index_in_dim(bins, feat[k], 0, keepdims=False)
        go_right = (leaf == src[k]) & (row.astype(jnp.int32) > thr[k])
        return jnp.where(go_right, k + 1, leaf)
    return jax.lax.fori_loop(0, n_splits, body,
                             jnp.zeros(bins.shape[1], jnp.int32))


@jax.jit
def _add_values(score, leaf, values, n_leaves):
    def body(l, out):
        return jnp.where(leaf == l, values[l], out)
    return score + jax.lax.fori_loop(0, n_leaves, body,
                                     jnp.zeros_like(score))


def leaf_ids(bins_dev, tree: dict):
    n = len(tree["split_feature"])
    pad = LEAF_PAD - 1 - n

    def arr(a):
        return jnp.asarray(np.pad(np.asarray(a, np.int32), (0, pad)))
    return _leaf_ids(bins_dev, arr(tree["split_feature"]),
                     arr(tree["threshold_bin"]), arr(split_sources(tree)),
                     jnp.int32(n))


def add_tree(score, bins_dev, tree: dict, values: np.ndarray):
    v = np.zeros(LEAF_PAD, np.float32)
    v[:len(values)] = values
    return _add_values(score, leaf_ids(bins_dev, tree), jnp.asarray(v),
                       jnp.int32(len(values)))


# -- gradients and histograms --------------------------------------------
@jax.jit
def binary_gradients(score, sign, sigmoid):
    """Binary log-loss on labels +-1 with the 2016 sigmoid parameter."""
    response = -2.0 * sign * sigmoid / (1.0 + jnp.exp(2.0 * sign * sigmoid
                                                     * score))
    a = jnp.abs(response)
    return response, a * (2.0 * sigmoid - a)


def _round(x, exponent_bits: int, mantissa_bits: int):
    """x rounded to a narrower float, still float32.  `reduce_precision` is
    the one rounding the TPU compiler may not take out: a convert to a
    narrow type and back it treats as excess precision it is free to keep
    (call 1 of PR 24 read the float8 control equal to the reference)."""
    return jax.lax.reduce_precision(x, exponent_bits, mantissa_bits)


def _three_bf16(x):
    """x as hi + mid + lo of bfloat16: products with a 0/1 one-hot are then
    exact on the MXU, with float32 accumulation."""
    hi = _round(x, 8, 7)
    mid = _round(x - hi, 8, 7)
    lo = _round(x - hi - mid, 8, 7)
    return [p.astype(jnp.bfloat16) for p in (hi, mid, lo)]


def _fp8(x):
    """float8 with 4 exponent and 3 mantissa bits (e4m3)."""
    return _round(x, 4, 3).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames="control")
def _leaf_histograms(bins, leaf, grad, hess, weight, control):
    """[F, Q * LEAF_PAD, BIN_PAD] sums over rows of quantity q in leaf l at
    bin b, with Q = 7 quantities: grad and hess as three bfloat16 parts
    each and the row weight (the count); with `control` two more, grad and
    hess rounded to float8.  Blocks of ROW_BLOCK rows; the blocks' sums
    are added with Kahan compensation, returned apart for a float64 sum
    on the host."""
    f, n = bins.shape
    quantities = (_three_bf16(grad * weight) + _three_bf16(hess * weight)
                  + [weight.astype(jnp.bfloat16)])
    if control:
        quantities += [_fp8(grad * weight), _fp8(hess * weight)]
    q = jnp.stack(quantities)                              # [Q, N]
    nq = q.shape[0]

    def body(i, carry):
        acc, comp = carry
        lo = i * ROW_BLOCK
        b = jax.lax.dynamic_slice(bins, (0, lo), (f, ROW_BLOCK))
        lf = jax.lax.dynamic_slice(leaf, (lo,), (ROW_BLOCK,))
        qb = jax.lax.dynamic_slice(q, (0, lo), (nq, ROW_BLOCK))
        leaf_hot = (jnp.arange(LEAF_PAD)[:, None] == lf[None, :])
        lhs = (qb[:, None, :] * leaf_hot[None, :, :].astype(jnp.bfloat16)
               ).reshape(nq * LEAF_PAD, ROW_BLOCK)
        bin_hot = (b[:, :, None].astype(jnp.int32)
                   == jnp.arange(BIN_PAD)[None, None, :]).astype(jnp.bfloat16)
        part = jnp.einsum("cr,frb->fcb", lhs, bin_hot,
                          preferred_element_type=jnp.float32)
        y = part - comp
        t = acc + y
        return t, (t - acc) - y

    zero = jnp.zeros((f, nq * LEAF_PAD, BIN_PAD), jnp.float32)
    return jax.lax.fori_loop(0, n // ROW_BLOCK, body, (zero, zero))


CHUNK_ROWS = 1024 * ROW_BLOCK    # rows per device call; float64 between


def leaf_histograms(bins_dev, leaf, grad, hess, weight, control: bool):
    """-> exact [L, F, B, 3] (grad, hess, count) and, with `control`,
    float8 [L, F, B, 2] (else None), float64 on the host, summed there
    over chunks of CHUNK_ROWS rows."""
    f, n = bins_dev.shape
    nq = 9 if control else 7
    h = np.zeros((f, nq * LEAF_PAD, BIN_PAD), np.float64)
    for lo in range(0, n, CHUNK_ROWS):
        hi = min(lo + CHUNK_ROWS, n)
        acc, comp = _leaf_histograms(bins_dev[:, lo:hi], leaf[lo:hi],
                                     grad[lo:hi], hess[lo:hi], weight[lo:hi],
                                     control)
        h += np.asarray(acc, np.float64) - np.asarray(comp, np.float64)
    h = h.reshape(f, nq, LEAF_PAD, BIN_PAD).transpose(2, 0, 3, 1)  # [L,F,B,Q]
    exact = np.stack([h[..., 0:3].sum(-1), h[..., 3:6].sum(-1), h[..., 6]],
                     axis=-1)
    return exact, (h[..., 7:9] if control else None)


# -- gains, on the host in float64 ---------------------------------------
def _leaf_gain(g, h, l2):
    return g * g / (h + l2)


def split_gains(hist, params):
    """[F, B] gain, over the parent's, of `bin <= b` left / `bin > b`
    right, -inf where the split is not allowed."""
    g, h, c = hist[..., 0], hist[..., 1], hist[..., 2]
    lg, lh, lc = np.cumsum(g, 1), np.cumsum(h, 1), np.cumsum(c, 1)
    tg, th, tc = lg[:, -1:], lh[:, -1:], lc[:, -1:]
    rg, rh, rc = tg - lg, th - lh, tc - lc
    l2 = params["lambda_l2"]
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = (_leaf_gain(lg, lh, l2) + _leaf_gain(rg, rh, l2)
                - _leaf_gain(tg, th, l2))
    ok = ((lc >= params["min_data_in_leaf"]) & (rc >= params["min_data_in_leaf"])
          & (lh >= params["min_sum_hessian_in_leaf"])
          & (rh >= params["min_sum_hessian_in_leaf"]) & (gain > 0.0))
    return np.where(ok, gain, -np.inf)


def check_tree(tree: dict, leaf_hist, leaf_hist8, params,
               scale: float) -> Dict[str, float]:
    """One tree's numbers.  `scale` is what the program multiplies a new
    tree's leaf outputs by.  With `leaf_hist8` (the float8 histograms) the
    control's numbers too, under `control.<name>`."""
    nl = len(tree["leaf_value"])
    left, right = tree["left_child"], tree["right_child"]
    # unit u: leaves are 0..nl-1, node k is nl + k
    unit = lambda child: nl + child if child >= 0 else ~child
    control = leaf_hist8 is not None
    hist = {l: leaf_hist[l] for l in range(nl)}
    hist8 = ({l: np.concatenate([leaf_hist8[l], leaf_hist[l][..., 2:]], -1)
              for l in range(nl)} if control else {})
    for k in range(nl - 2, -1, -1):
        a, b = unit(int(left[k])), unit(int(right[k]))
        hist[nl + k] = hist[a] + hist[b]
        if control:
            hist8[nl + k] = hist8[a] + hist8[b]
    best, best8_true = {}, {}

    def open_unit(u):
        best[u] = split_gains(hist[u], params).max()
        if control:
            g8 = split_gains(hist8[u], params)
            pick = np.unravel_index(np.argmax(g8), g8.shape)
            # the control's choice, valued by the exact histograms
            best8_true[u] = (g8.max(), split_gains_at(hist[u], params, pick))

    open_unit(nl)
    frontier = [nl]
    gap, gap8, lost, lost8, offered = 0.0, 0.0, 0.0, 0.0, 0.0
    for k in range(nl - 1):
        top = max(best[u] for u in frontier)
        chosen = split_gains_at(hist[nl + k], params,
                                (int(tree["split_feature"][k]),
                                 int(tree["threshold_bin"][k])))
        gap = max(gap, (top - chosen) / top)
        lost += top - chosen
        offered += top
        if control:
            u8 = max(frontier, key=lambda u: best8_true[u][0])
            gap8 = max(gap8, (top - best8_true[u8][1]) / top)
            lost8 += top - best8_true[u8][1]
        frontier.remove(nl + k)
        for child in (int(left[k]), int(right[k])):
            u = unit(child)
            frontier.append(u)
            open_unit(u)
    tot = np.stack([hist[l][0].sum(0) for l in range(nl)])   # [L, 3]
    want = -tot[:, 0] / (tot[:, 1] + params["lambda_l2"]) * scale
    got = np.asarray(tree["leaf_value"], np.float64)
    rows = tot[:, 2]
    counts = np.asarray(tree["leaf_count"], np.float64)

    def update_gap(values):
        """The tree's score update, program against reference, as norms
        over the ROWS: sqrt(sum rows * diff^2 / sum rows * want^2)."""
        return float(np.sqrt((rows * (values - want) ** 2).sum()
                             / (rows * want ** 2).sum()))

    def widest_gap(values):
        return float(np.abs(values - want).max() / np.abs(want).max())

    numbers = {
        "gain_loss": float(lost / offered),
        "leaf_update_gap": update_gap(got),
        "leaf_count_gap": float((np.abs(counts - rows)
                                 / np.maximum(rows, 1.0)).max()),
        "gain_gap": float(gap),
        "leaf_value_gap": widest_gap(got),
    }
    if control:
        tot8 = np.stack([hist8[l][0].sum(0) for l in range(nl)])
        want8 = -tot8[:, 0] / (tot8[:, 1] + params["lambda_l2"]) * scale
        numbers.update({
            "control.gain_loss": float(lost8 / offered),
            "control.leaf_update_gap": update_gap(want8),
            "control.gain_gap": float(gap8),
            "control.leaf_value_gap": widest_gap(want8),
        })
    return numbers


def split_gains_at(hist, params, pick) -> float:
    """The exact gain of one (feature, threshold bin), allowed or not."""
    f, b = pick
    g, h = hist[f, :, 0], hist[f, :, 1]
    l2 = params["lambda_l2"]
    lg, lh = g[:b + 1].sum(), h[:b + 1].sum()
    tg, th = g.sum(), h.sum()
    with np.errstate(divide="ignore", invalid="ignore"):
        return float(_leaf_gain(lg, lh, l2) + _leaf_gain(tg - lg, th - lh, l2)
                     - _leaf_gain(tg, th, l2))


# -- the comparison --------------------------------------------------------
def compare(bins: np.ndarray, label: np.ndarray, params: dict,
            produced: Produced, checked: Sequence[int],
            control: bool = False) -> Dict[str, float]:
    """All numbers of one run; `checked` are the trees whose growth is
    recomputed.  Needs the device free of the program's state."""
    trees = produced.trees
    n_trees = len(trees)
    f, n = bins.shape
    n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
    bins_dev = jnp.pad(jnp.asarray(bins), ((0, 0), (0, n_pad - n)))
    pad1 = lambda a: jnp.asarray(np.pad(a, (0, n_pad - n)))
    sign = pad1(np.where(label > 0.5, 1.0, -1.0).astype(np.float32))
    weight = pad1(np.ones(n, np.float32))

    numbers: Dict[str, float] = {}
    score, upto = jnp.zeros(n_pad, jnp.float32), 0
    for t in sorted(set(checked)) + [n_trees]:
        for j in range(upto, t):
            score = add_tree(score, bins_dev, trees[j],
                             np.asarray(trees[j]["leaf_value"], np.float64))
        upto = t
        if t == n_trees:
            break
        grad, hess = binary_gradients(score, sign,
                                      jnp.float32(params["sigmoid"]))
        exact, eighth = leaf_histograms(bins_dev, leaf_ids(bins_dev, trees[t]),
                                        grad, hess, weight, control)
        for k, v in check_tree(trees[t], exact, eighth, params,
                               params["learning_rate"]).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    total = np.asarray(score)[:n]
    numbers["score_gap"] = float(np.abs(produced.scores - total).max()
                                 / max(np.abs(total).max(), 1e-30))
    numbers["trees_missing"] = float(produced.trees_asked - n_trees)
    return numbers


def as_control(numbers: Dict[str, float]) -> Dict[str, float]:
    """The run's numbers with the control's in the program's place."""
    control = {k[len("control."):]: v for k, v in numbers.items()
               if k.startswith("control.")}
    return dict(numbers, **control)


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, {name: [number, limit]}); every limit's number has to
    be there and at or under it."""
    compared = {k: [numbers.get(k), lim] for k, lim in limits.items()}
    ok = all(v is not None and np.isfinite(v) and v <= lim
             for v, lim in compared.values())
    return ok, compared
