"""Faults planted under a class-wise job, for the tests and for
`chip_fault_multi.py`, which reads them on the chip at the cell's own size.
Each takes the booster before its warm-up.  Three replace a function in the
program's module, where the step looks it up when it is traced: the steps
are kept by a key that does not know of the fault, so a sound booster made
later in the same process needs the program's step cache cleared and the
functions put back (the tests; `PATCHED` names them)."""

from __future__ import annotations

# what a fault may replace in lightgbm_tpu.models.gbdt
PATCHED = ("_fused_step_multi_body", "_resort_rows")


def _step_body(fault: str):
    """The program's class-wise step body (`_fused_step_multi_body`, no
    valid sets) with `fault` in it: `per_class` computes each class's
    gradients from the scores that the iteration's earlier classes have
    already moved; `next_class` adds a class's leaf values to the next
    class's scores."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.models import gbdt

    def make(grad_fn, grow_kw, lr, dtype, reorder, row_state,
             compact_rows=0):
        def step(scores, valid_scores, bag_masks, fmasks, bins, valid_bins,
                 gstate, stopped, *row_order):
            grad, hess = grad_fn(scores, gstate)
            k = grad.shape[0]

            def body(carry, xs):
                sc, stop = carry
                cls, g, h, bag, fm = xs
                if fault == "per_class":
                    g, h = (a[cls] for a in grad_fn(sc, gstate))
                tree, leaf_id = gbdt.grow_tree_bagged(
                    bins, g.astype(dtype), h.astype(dtype), bag, fm,
                    bag_rows=compact_rows, **grow_kw)
                vals = jnp.where(stop, 0.0, tree.leaf_value * lr).astype(
                    jnp.float32)
                to = (cls + 1) % k if fault == "next_class" else cls
                sc = sc.at[to].add(vals[leaf_id])
                ints, floats = gbdt._pack_tree(tree)
                stop = stop | (tree.num_leaves <= 1)
                return (sc, stop), (ints, floats, leaf_id)

            (scores, stopped), (ints_k, floats_k, leaf_k) = jax.lax.scan(
                body, (scores, stopped),
                (jnp.arange(k, dtype=jnp.int32), grad, hess, bag_masks,
                 fmasks))
            if not reorder:
                return scores, list(valid_scores), ints_k, floats_k, stopped
            n = bins.shape[1]
            m = compact_rows if 0 < compact_rows < n else n
            (bins, scores, bag_masks, order), gstate = gbdt._resort_rows(
                gbdt._class_key(leaf_k, m, grow_kw["max_leaves"]),
                [bins, scores, bag_masks, row_order[0]], gstate, row_state)
            return (scores, list(valid_scores), ints_k, floats_k, stopped,
                    bins, bag_masks, gstate, order)
        return step
    return make


def per_class_gradients(booster):
    """Each class's gradients recomputed after the trees of the classes
    before it in the same iteration (upstream computes them once, from the
    scores before the iteration)."""
    from lightgbm_tpu.models import gbdt
    gbdt._fused_step_multi_body = _step_body("per_class")


def next_class_scores(booster):
    """A class's leaf values added to the NEXT class's scores."""
    from lightgbm_tpu.models import gbdt
    gbdt._fused_step_multi_body = _step_body("next_class")


def scores_not_permuted(booster):
    """A re-sort moves every per-row array but the [K, N] scores, which
    then belong to other rows."""
    from lightgbm_tpu.models import gbdt
    real = gbdt._resort_rows

    def resort(keys, bufs, gstate, row_state):
        moved, gstate = real(keys, bufs, gstate, row_state)
        if bufs[1].ndim == 2 and bufs[1].shape[0] > 1:
            moved = [moved[0], bufs[1]] + list(moved[2:])
        return moved, gstate
    gbdt._resort_rows = resort


def hessian_halved(booster):
    """h = p (1 - p) in the place of 2 p (1 - p)."""
    objective = booster.objective
    make = objective.make_grad_fn

    def make_halved():
        grad_fn = make()

        def halved(score, state):
            grad, hess = grad_fn(score, state)
            return grad, hess * 0.5
        return halved
    objective.make_grad_fn = make_halved


FAULTS = {"per_class_gradients": per_class_gradients,
          "next_class_scores": next_class_scores,
          "scores_not_permuted": scores_not_permuted,
          "hessian_halved": hessian_halved}
# the number each is caught by
CAUGHT_BY = {"per_class_gradients": "leaf_update_gap",
             "next_class_scores": "score_gap",
             "scores_not_permuted": "score_gap",
             "hessian_halved": "leaf_update_gap"}
