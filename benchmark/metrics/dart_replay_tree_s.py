"""Device seconds a tree of the DART cell's traced window spent under
`lgbm.dart_replay`: the leaf ids of a dropped tree that lies outside the leaf
bank, by a replay of its splits over the rows (inside the drop or the
normalise; a reader takes the last component).  0 while every tree is banked.
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "dart_replay_tree_s")
