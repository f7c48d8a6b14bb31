"""`reference.py` for rows that are sharded over chips: the same recomputation,
shard by shard, one chip a shard, the shards' histograms added on the host.

The rows of a four-chip host's share (273M x 39 B of bins and 3.3 GB of
per-row floats) do not fit one chip, and one chip would take four times as
long.  So the rows are cut into as many contiguous parts as there are
devices (the reference's own cut, equal parts: sums over all rows do not
depend on it), and each part goes through `reference.py`'s functions on its
own device, in a thread of its own so that the chips work side by side:
every tree's splits replayed to leaf ids and scores, and for the checked
trees the gradients and the per-leaf histograms with exact products.  The
parts' histograms are added in float64 on the host; `check_tree` and
`judge` then see what they see on one chip.  It imports nothing of the
program, and the numbers are the same five, over all rows of all shards.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from harness import reference
from harness.reference import ROW_BLOCK, Produced


def shard_bounds(n: int, shards: int) -> List[Tuple[int, int]]:
    """Contiguous parts of n rows, equal to within a row."""
    cuts = [n * i // shards for i in range(shards + 1)]
    return list(zip(cuts[:-1], cuts[1:]))


def _shard_pass(device, bins: np.ndarray, label: np.ndarray, params: dict,
                trees: List[dict], checked: Sequence[int], control: bool):
    """One part of the rows on one device -> ({tree: (exact, eighth)} its
    per-leaf histograms of the checked trees, [n] the summed scores)."""
    with jax.default_device(device):
        n = bins.shape[1]
        n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
        bins_dev = jnp.pad(jax.device_put(bins, device),
                           ((0, 0), (0, n_pad - n)))
        pad1 = lambda a: jnp.pad(jax.device_put(a, device), (0, n_pad - n))
        sign = pad1(np.where(label > 0.5, 1.0, -1.0).astype(np.float32))
        weight = pad1(np.ones(n, np.float32))
        hists = {}
        score, upto = jnp.zeros(n_pad, jnp.float32), 0
        for t in sorted(set(checked)) + [len(trees)]:
            for j in range(upto, t):
                score = reference.add_tree(
                    score, bins_dev, trees[j],
                    np.asarray(trees[j]["leaf_value"], np.float64))
            upto = t
            if t == len(trees):
                break
            grad, hess = reference.binary_gradients(
                score, sign, jnp.float32(params["sigmoid"]))
            hists[t] = reference.leaf_histograms(
                bins_dev, reference.leaf_ids(bins_dev, trees[t]), grad, hess,
                weight, control)
        return hists, np.asarray(score)[:n]


def summed_histograms(bins: np.ndarray, label: np.ndarray, params: dict,
                      trees: List[dict], checked: Sequence[int],
                      control: bool, devices) -> Tuple[Dict, np.ndarray]:
    """-> ({tree: (exact [L, F, B, 3], eighth [L, F, B, 2] or None)} over
    ALL rows, float64; [N] the trees' summed scores, file order)."""
    bounds = shard_bounds(bins.shape[1], len(devices))
    with ThreadPoolExecutor(len(devices)) as pool:
        parts = list(pool.map(
            lambda a: _shard_pass(a[0], bins[:, a[1][0]:a[1][1]],
                                  label[a[1][0]:a[1][1]], params, trees,
                                  checked, control),
            zip(devices, bounds)))
    hists: Dict[int, Tuple[np.ndarray, Optional[np.ndarray]]] = {}
    for t in sorted(set(checked)):
        exact = sum(p[0][t][0] for p in parts)
        eighth = sum(p[0][t][1] for p in parts) if control else None
        hists[t] = (exact, eighth)
    return hists, np.concatenate([p[1] for p in parts])


def compare(bins: np.ndarray, label: np.ndarray, params: dict,
            produced: Produced, checked: Sequence[int], devices,
            control: bool = False) -> Dict[str, float]:
    """All numbers of one run, as `reference.compare` gives them; the rows
    go to `devices` part by part.  Needs the devices free of the
    program's state."""
    trees = produced.trees
    hists, total = summed_histograms(bins, label, params, trees, checked,
                                     control, devices)
    numbers: Dict[str, float] = {}
    for t, (exact, eighth) in hists.items():
        for k, v in reference.check_tree(trees[t], exact, eighth, params,
                                         params["learning_rate"]).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    numbers["score_gap"] = float(np.abs(produced.scores - total).max()
                                 / max(np.abs(total).max(), 1e-30))
    numbers["trees_missing"] = float(produced.trees_asked - len(trees))
    return numbers
