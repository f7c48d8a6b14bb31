"""Device seconds a tree of the DART cell's traced window spent under
`lgbm.dart_carry`: the leaf bank's filled groups through a re-sort's
permutation, over ALL the window's trees.  A PART of `resort_tree_s.dart`
(`device_parts` of harness/scopes_dart.json), not beside it.
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "dart_carry_tree_s")
