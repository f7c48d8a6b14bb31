"""The work lambdarank's pair pass needs for one tree, counted once from
the reference's formula (`reference_ranked.py`), whatever implements it.

A query of L documents has L x L ordered cells (i, j).  Taken as the
formula is written, without the symmetry a cleverer pass could use, a cell
costs:

  the rank count          s_j > s_i, s_j = s_i, j < i, or/and, add       5
  ds, |ds|, 0.01 + |ds|                                                  3
  g_i - g_j, disc_i - disc_j, its |.|, the two products with 1/maxDCG    5
  delta / (0.01 + |ds|)                                                  1
  2 sigma ds, exp, 1 + ., 2 / .                                          4
  p delta, 2 - p, p (2 - p), 2 delta, their product                      5
  grade_i > grade_j and the two selects                                  3
  the four sums (lambda and hessian of both documents)                   4

OPS_PER_CELL = 30, the exp and each divide one operation.  Padding is not
work: the count is over the queries' own lengths, so a pass that evaluates
padded cells reads a lower share of its roofline.  The least time is the
operations over the vector unit's peak (`peaks_vector.json`; the pass
reads 20 bytes a DOCUMENT and writes 8, so bytes never bind it).
"""

from __future__ import annotations

import json
import os

import numpy as np

OPS_PER_CELL = 30
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks_vector.json")


def pair_cells(query_lengths) -> int:
    """Sum over the queries of L^2."""
    return int((np.asarray(query_lengths, np.int64) ** 2).sum())


def pair_ops(query_lengths) -> int:
    """Operations of one tree's pair pass."""
    return pair_cells(query_lengths) * OPS_PER_CELL


def vector_peak(device_kind: str) -> float:
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError("no vector peak for device kind %r in %s"
                       % (device_kind, PEAKS_FILE))
    return float(table[device_kind]["vector_ops_per_s"])


def tree_least_seconds(query_lengths, device_kind: str) -> float:
    """The least time one tree's pair pass could take on the chip."""
    return pair_ops(query_lengths) / vector_peak(device_kind)
