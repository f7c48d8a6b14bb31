"""Faults planted under a booster that samples rows and features, for the
tests and for `chip_fault_bagged.py`, which reads them on the chip at the
cell's own size.  Each takes the booster before its warm-up.  The first two
replace `grow_tree_bagged` in the program's module, where the step bodies
look it up when they are traced: the steps are kept by a key that does not
know of the fault, so a sound booster made later in the same process needs
the program's step cache cleared and the function put back (the tests)."""

from __future__ import annotations

import numpy as np


def oob_descent_left_out(booster):
    """The rows outside the compacted window do not descend the finished
    tree: each takes leaf 0's value, so an out-of-bag row's score does not
    move with the leaf it falls in."""
    from lightgbm_tpu.models import gbdt
    real = gbdt.grow_tree_bagged

    def grow_tree_bagged(bins, grad, hess, bag, fmask, *, bag_rows=0, **kw):
        tree, leaf = real(bins, grad, hess, bag, fmask, bag_rows=bag_rows,
                          **kw)
        if 0 < bag_rows < bins.shape[1]:
            leaf = leaf.at[bag_rows:].set(0)
        return tree, leaf
    gbdt.grow_tree_bagged = grow_tree_bagged


def hist_over_all_rows(booster):
    """The bag is drawn, arranged and reported, and then ignored: every
    tree's histograms are over all rows."""
    import jax.numpy as jnp
    from lightgbm_tpu.models import gbdt
    real = gbdt.grow_tree_bagged

    def grow_tree_bagged(bins, grad, hess, bag, fmask, *, bag_rows=0, **kw):
        return real(bins, grad, hess, jnp.ones_like(bag), fmask, bag_rows=0,
                    **kw)
    gbdt.grow_tree_bagged = grow_tree_bagged


def first_bag_kept(booster):
    """The bag of epoch 0 is kept at every epoch: no later draw is made."""
    real = booster._bagging

    def bagging(it, cls):
        if it == 0:
            real(it, cls)
    booster._bagging = bagging


def feature_mask_ignored(booster):
    """Every tree may split on every feature."""
    features = booster.train_data.num_features
    booster._feature_mask = lambda cls: np.ones(features, dtype=bool)


FAULTS = {"oob_descent_left_out": oob_descent_left_out,
          "hist_over_all_rows": hist_over_all_rows,
          "first_bag_kept": first_bag_kept,
          "feature_mask_ignored": feature_mask_ignored}
# the number each is caught by
CAUGHT_BY = {"oob_descent_left_out": "score_gap",
             "hist_over_all_rows": "leaf_count_gap",
             "first_bag_kept": "bag_gap",
             "feature_mask_ignored": "feature_gap"}
