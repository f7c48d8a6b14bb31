"""`score_update_tree_s` of the DART cell: device seconds a tree spent under
`lgbm.score_update`, `valid_update` and `pack_tree`, WITHOUT DART's own
scopes (the drop, the normalise, the replay and the bank's append have
metrics of their own).
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "score_update_tree_s.dart")
