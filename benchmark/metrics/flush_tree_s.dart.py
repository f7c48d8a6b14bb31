"""`flush_tree_s` of the DART cell: the summed durations of the program's
`lgbm.flush` spans in the traced window over its trees (the f64 replay of
the trees' drop factors included).
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "flush_tree_s.dart")
