"""Faults planted under the timed path, for the tests here and for
`chip_fault.py`, which reads them on the chip at a cell's own size.  Each
takes the booster before its warm-up and breaks it in place."""

from __future__ import annotations

import numpy as np


def state_unchanged(after_trees: int):
    """From `after_trees` trees on, a step returns at once: no tree, the
    scores as they were."""
    def plant(booster):
        real = booster.train_segment
        done = [0]

        def train_segment(max_iters, is_eval=True):
            if done[0] >= after_trees:
                return False, max_iters
            stop, k = real(max_iters, is_eval)
            done[0] += k
            return stop, k
        booster.train_segment = train_segment
    return plant


def half_batch(booster):
    """The second half of the rows is left out of every histogram; sums and
    means are over the first half alone."""
    n = booster.num_data
    for mask in booster.bag_masks:
        mask[n // 2:] = False
    booster._bag_dev = [None] * booster.num_class
    booster._bag_dev_packed = [None] * booster.num_class


def altered_answer(booster):
    """Every tree leaves the device with its largest leaf value negated,
    after the scores were updated with the true one."""
    real = booster._unpack_tree

    def unpack(pending):
        tree = real(pending)
        worst = int(np.argmax(np.abs(tree.leaf_value)))
        tree.leaf_value = tree.leaf_value.copy()
        tree.leaf_value[worst] = -tree.leaf_value[worst]
        return tree
    booster._unpack_tree = unpack


FAULTS = {"half_batch": half_batch, "altered_answer": altered_answer}
