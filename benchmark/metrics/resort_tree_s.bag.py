"""`resort_tree_s` of the bagged cell: device seconds under `lgbm.resort`
ALONE (the arrangement is `bag_arrange_tree_s`), over ALL the traced
window's trees.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "resort_tree_s.bag")
