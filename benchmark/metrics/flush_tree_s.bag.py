"""`flush_tree_s` of the bagged cell: the summed durations of the program's
`lgbm.flush` spans in the traced window over its trees.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "flush_tree_s.bag")
