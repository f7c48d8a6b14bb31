"""Device seconds a tree of the DART cell's traced window spent under
`lgbm.dart_normalize`: the dropped trees put back on the scores, shrunk by
k / (1 + k), after the new tree (a replayed tree's descent is
`dart_replay_tree_s`).
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "dart_normalize_tree_s")
