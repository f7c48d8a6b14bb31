"""`objective_tree_s` of the class-wise cell: device seconds a tree spent under
lgbm.objective (the softmax of all classes, once an iteration, and the casts).
Grouped in harness/scopes_multi.json; nothing where the trace has
nothing of it to read (harness/scopes_multi.py)."""

from harness import scopes_multi


def read(record: dict):
    return scopes_multi.tree_seconds(record, "objective_tree_s.multi")
