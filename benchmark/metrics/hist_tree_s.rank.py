"""`hist_tree_s` of a ranking cell: device seconds a tree of the traced
window spent on histograms (root sweep, block list, sweeps, pool,
exchange).
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "hist_tree_s.rank")
