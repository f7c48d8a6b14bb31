"""Device seconds a tree of the DART cell's traced window spent under
`lgbm.dart_drop`: the dropped trees taken off the scores before the
gradients, a look-up of each row's leaf id in the bank and an add a dropped
tree (a replayed tree's descent is `dart_replay_tree_s`).
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "dart_drop_tree_s")
