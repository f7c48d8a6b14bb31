"""`hist_tree_s` of the DART cell: device seconds a tree spent under
`lgbm.hist_root`, `block_list`, `hist_sweep`, `hist_pool` and
`hist_exchange`.
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "hist_tree_s.dart")
