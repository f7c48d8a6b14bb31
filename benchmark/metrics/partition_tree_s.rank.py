"""`partition_tree_s` of a ranking cell: device seconds a tree of the traced
window spent under `lgbm.partition`, `tree_update`, `oob_descent`, and
under `lgbm.grow` in no deeper scope.
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "partition_tree_s.rank")
