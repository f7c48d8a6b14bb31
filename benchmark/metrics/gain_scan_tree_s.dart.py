"""`gain_scan_tree_s` of the DART cell: device seconds a tree spent under
`lgbm.gain_scan`.
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "gain_scan_tree_s.dart")
