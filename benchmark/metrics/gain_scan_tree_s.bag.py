"""`gain_scan_tree_s` of the bagged cell: device seconds a tree spent under
`lgbm.gain_scan` (the scan is over all 39 features; the tree's mask rules
the others out).
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "gain_scan_tree_s.bag")
