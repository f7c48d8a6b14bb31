"""The work the algorithm needs for a set of grown trees, counted once,
whatever implements it.

A leaf-wise tree with histogram subtraction has to visit every in-bag row
once for the root's histogram and, at each split, the rows of the SMALLER
child (the larger child's histogram is the parent's less the smaller's).
A visit reads the row's F bin bytes, its gradient and hessian (8 B) and
its row index (4 B), and makes 3 accumulates (gradient, hessian, count)
per feature, 2 operations each.  The least time is what the chip would
need for that at its peaks: the larger of bytes over peak bytes/s and
operations over peak operations/s.
"""

from __future__ import annotations

import json
import os
from typing import Iterable

import numpy as np

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def row_visits(tree: dict, in_bag_rows: int) -> int:
    """Root rows + the smaller child's rows at every split."""
    left, right = tree["left_child"], tree["right_child"]
    leaf_count = np.asarray(tree["leaf_count"], np.int64)
    node_count = np.zeros(len(left), np.int64)

    def count(child: int) -> int:
        return int(node_count[child] if child >= 0 else leaf_count[~child])

    visits = int(in_bag_rows)
    for k in range(len(left) - 1, -1, -1):
        a, b = count(int(left[k])), count(int(right[k]))
        node_count[k] = a + b
        visits += min(a, b)
    return visits


def work(trees: Iterable[dict], in_bag_rows: int, features: int) -> dict:
    visits = sum(row_visits(t, in_bag_rows) for t in trees)
    return {"row_visits": visits,
            "bytes": visits * (features + 8 + 4),
            "ops": visits * features * 3 * 2}


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in %s"
                       % (device_kind, PEAKS_FILE))
    return table[device_kind]


def least_seconds(w: dict, device_kind: str) -> dict:
    p = peaks(device_kind)
    by_bytes = w["bytes"] / p["bytes_per_s"]
    by_ops = w["ops"] / p["flops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bytes" if by_bytes >= by_ops else "ops"}


def window_least_seconds(record: dict) -> float:
    """The least time for the trees a run's window grew (the one count
    that the step's and every kernel's share are taken from)."""
    w = work(record["window_trees"], record["in_bag_rows"],
             record["features"])
    return least_seconds(w, record["device_kind"])["seconds"]
