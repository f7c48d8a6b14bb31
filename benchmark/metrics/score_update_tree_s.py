"""Device seconds a tree of the traced window spent in the boosting step
outside the grower: score and validation updates, tree packing, DART's
bank surgery (harness/scopes.json).  Nothing where the trace has no
`lgbm.*` scope."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "score_update_tree_s")
