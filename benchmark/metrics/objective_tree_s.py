"""Device seconds a tree of the traced window spent under `lgbm.objective`:
the objective's gradients and their casts.  Nothing where the trace has
no `lgbm.*` scope (harness/scopes.py)."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "objective_tree_s")
