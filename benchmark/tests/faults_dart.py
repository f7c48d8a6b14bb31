"""Faults planted under a booster that boosts with dropouts, for the tests and
for `chip_fault_dart.py`, which reads them on the chip at the cell's own size.
Each takes the booster before its warm-up.  Four replace a function in the
program's module, where the step looks it up when it is traced: the steps
are kept by a key that does not know of the fault, so a sound booster made
later in the same process needs the program's step cache cleared and the
functions put back (the tests; `PATCHED` names them)."""

from __future__ import annotations

# what a fault may replace in lightgbm_tpu.models.gbdt
PATCHED = ("_dart_drop_trees", "_dart_normalize_trees", "_carry_filled",
           "_dart_replayed_ids")


def undropped_gradients(booster):
    """The gradients are taken at the scores WITH the dropped trees: the
    drop phase leaves the scores alone (the bank's values are still
    negated), and the trees come off only after the new tree is grown, just
    before the normalise puts them back."""
    import jax.numpy as jnp
    from lightgbm_tpu.models import gbdt
    drop, normalize = gbdt._dart_drop_trees, gbdt._dart_normalize_trees

    def late_drop(scores, bank_f, *rest):
        _, bank_f, kept = drop(scores, bank_f, *rest)
        return scores, bank_f, kept

    def drop_then_normalize(scores, vss, bank_f, bank_i, leaf_bank, vbanks,
                            bins, drop_idx, drops, lr, kf, kept, L):
        dropped = jnp.arange(drop_idx.shape[0]) < drops
        back = bank_f.at[drop_idx].multiply(
            jnp.where(dropped, -1.0, 1.0).astype(bank_f.dtype)[:, None],
            mode="drop", unique_indices=False)
        # (a drop list names a tree once, and the padding names tree 0 with
        # a factor of one)
        scores, _, _ = drop(scores, back, bank_i, leaf_bank, bins, drop_idx,
                            drops, L, len(kept))
        return normalize(scores, vss, bank_f, bank_i, leaf_bank, vbanks, bins,
                         drop_idx, drops, lr, kf, kept, L)

    gbdt._dart_drop_trees = late_drop
    gbdt._dart_normalize_trees = drop_then_normalize


def normalize_left_out(booster):
    """The dropped trees never come back: the normalise step is left out,
    while the host goes on scaling the delivered trees by k / (1 + k)."""
    from lightgbm_tpu.models import gbdt

    def nothing(scores, vss, bank_f, *rest):
        return scores, tuple(vss), bank_f
    gbdt._dart_normalize_trees = nothing


def learning_rate_for_shrinkage(booster):
    """A new tree is shrunk by `learning_rate`, as plain boosting shrinks
    it, in the place of 1 / (1 + k)."""
    draw = booster._draw_drops

    def draw_drops(it):
        draw(it)
        booster.shrinkage_rate = booster.config.learning_rate
    booster._draw_drops = draw_drops


def bank_not_carried(booster):
    """A re-sort moves the rows and leaves the leaf bank where it lay: the
    drops and normalises after it read other rows' leaf ids."""
    from lightgbm_tpu.models import gbdt
    gbdt._carry_filled = lambda bank, *rest: bank.rows


def other_drop_seed(booster):
    """The lottery runs over another seed's stream."""
    from lightgbm_tpu.utils.mt19937 import Mt19937Random
    booster.drop_rng = Mt19937Random(booster.config.drop_seed + 1)


def small_bank(booster):
    """NO fault: the leaf bank cut to one re-sort interval's trees less one
    (31 at the cell's size, 3 at a period under 16 trees), so that the
    window's later drops lie outside it and are replayed.  Sound, and the
    setting of `replay_reads_row0`."""
    rows = 32 if booster.reorder_every >= 16 else 4
    booster._bank_plan = (rows, type(booster)._REPLAY_SLOTS)


def replay_reads_row0(booster):
    """A replayed tree's leaf ids are tree 0's (the bank's row 0), under a
    bank small enough that the window replays (`small_bank`)."""
    from lightgbm_tpu.models import gbdt
    small_bank(booster)
    replayed = gbdt._dart_replayed_ids
    gbdt._dart_replayed_ids = (
        lambda bank_i, j, bins, L: replayed(bank_i, 0, bins, L))


FAULTS = {"undropped_gradients": undropped_gradients,
          "normalize_left_out": normalize_left_out,
          "learning_rate_for_shrinkage": learning_rate_for_shrinkage,
          "bank_not_carried": bank_not_carried,
          "other_drop_seed": other_drop_seed,
          "replay_reads_row0": replay_reads_row0}
SOUND = {"small_bank": small_bank}
# the number each is caught by
CAUGHT_BY = {"undropped_gradients": "leaf_update_gap",
             "normalize_left_out": "score_gap",
             "learning_rate_for_shrinkage": "leaf_update_gap",
             "bank_not_carried": "score_gap",
             "other_drop_seed": "drop_gap",
             "replay_reads_row0": "score_gap"}
