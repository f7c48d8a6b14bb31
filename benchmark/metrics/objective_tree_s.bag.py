"""`objective_tree_s` of the bagged cell: device seconds a tree spent under
`lgbm.objective` (gradients of ALL rows, in the bag or out).
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "objective_tree_s.bag")
