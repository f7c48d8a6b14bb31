"""Device seconds a tree of the traced window spent in the grow scan's own
work: partition compares, tree arrays, the out-of-bag descent, and what
sits under `lgbm.grow` in no deeper scope (harness/scopes.json).  Nothing
where the trace has no `lgbm.*` scope."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "partition_tree_s")
