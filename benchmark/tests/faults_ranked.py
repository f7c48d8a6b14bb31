"""Faults planted under a ranking booster (`objective=lambdarank`, the
device path), for the tests and for `chip_fault_ranked.py`, which reads
them on the chip at a cell's own size.  Each takes the booster before its
warm-up and breaks the objective it holds."""

from __future__ import annotations

import numpy as np


def long_queries_left_out(booster):
    """The queries longer than the median take no part in the pair pass:
    their documents' lambdas and hessians are zero in every tree."""
    import jax.numpy as jnp
    objective = booster.objective
    di, lab, gain, inv, wts, row_slot, disc = objective._dev_state
    lengths = (np.asarray(lab) >= 0).sum(-1)               # [blocks, QB]
    out = lengths > np.median(lengths[lengths > 0])
    lab = jnp.where(jnp.asarray(out)[..., None], -1, lab)
    objective._dev_state = (di, lab, gain, inv, wts, row_slot, disc)


def stale_positions(booster):
    """The query blocks' row positions are NOT remapped after a re-sort:
    from the second tree on a document's score is read where the document
    lay before the rows moved.  The steps are traced at their first call
    and kept by the objective's key, so a sound booster made later in the
    same process needs the program's step cache cleared (the tests)."""
    def make_row_state_fn():
        def row_state(gstate):
            di, lab, gain, inv, wts, row_slot, disc = gstate
            return [row_slot], lambda moved, rel: (
                di, lab, gain, inv, wts, moved[0], disc)
        return row_state
    booster.objective.make_row_state_fn = make_row_state_fn


FAULTS = {"long_queries_left_out": long_queries_left_out,
          "stale_positions": stale_positions}
