"""`resort_tree_s` of the DART cell: device seconds a tree spent under
`lgbm.resort` WITH the leaf bank's carry (`lgbm.dart_carry`,
also read alone as `dart_carry_tree_s`), over ALL the traced window's trees
(the amortised cost).
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "resort_tree_s.dart")
