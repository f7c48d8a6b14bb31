"""Device seconds a tree of the DART cell's traced window spent under
`lgbm.dart_bank`: the append alone, a new tree's packed rows and its leaf
ids written into the banks.
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "dart_bank_tree_s")
