"""Device seconds a tree of the bagged cell's traced window spent under
`lgbm.oob_descent`: the descent of the rows outside the compacted window
through the finished tree, for the score update.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "oob_descent_tree_s")
