"""Device seconds of the ordered-partition re-sort and the bag arrangement
over ALL the traced window's trees: the amortised cost of a phase that
runs once a period (harness/scopes.json).  Nothing where the trace has no
`lgbm.*` scope."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "resort_tree_s")
