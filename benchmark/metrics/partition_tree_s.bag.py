"""`partition_tree_s` of the bagged cell: device seconds a tree spent under
`lgbm.partition`, `tree_update` and `lgbm.grow` in no deeper scope;
WITHOUT the out-of-bag descent, which is `oob_descent_tree_s`.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "partition_tree_s.bag")
