"""`hist_tree_s` of the bagged cell: device seconds a tree spent under
`lgbm.hist_root`, `block_list`, `hist_sweep`, `hist_pool` and
`hist_exchange`, the sweeps over the compacted window.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "hist_tree_s.bag")
