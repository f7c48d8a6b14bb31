"""Faults planted under a row-sharded booster (`tree_learner=data`), for the
tests and for `chip_fault_sharded.py`, which reads them on the chips at a
cell's own size.  Each takes the booster before its warm-up."""

from __future__ import annotations


def shard_left_out(booster):
    """The last shard's rows are left out of every histogram: sums and
    counts are over the other shards' rows alone."""
    block = booster.n_pad // booster.grower.num_shards
    for mask in booster.bag_masks:
        mask[booster.n_pad - block:] = False
    booster._bag_dev = [None] * booster.num_class
    booster._bag_dev_packed = [None] * booster.num_class


def no_exchange(booster):
    """The exchange is left out: `psum` gives back what it is given, so
    each shard grows its trees from its own histograms.  The steps are
    traced at their first call, so the name stays replaced for the rest
    of the process; `restore_exchange` puts it back (the tests)."""
    import jax
    if not hasattr(no_exchange, "real"):
        no_exchange.real = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **_kw: x


def restore_exchange():
    import jax
    if hasattr(no_exchange, "real"):
        jax.lax.psum = no_exchange.real
        del no_exchange.real


FAULTS = {"shard_left_out": shard_left_out, "no_exchange": no_exchange}
