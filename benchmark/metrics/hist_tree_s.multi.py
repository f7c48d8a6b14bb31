"""`hist_tree_s` of the class-wise cell: device seconds a tree spent under
lgbm.hist_root, block_list, hist_sweep, hist_pool and hist_exchange (sweeps at F = 784).
Grouped in harness/scopes_multi.json; nothing where the trace has
nothing of it to read (harness/scopes_multi.py)."""

from harness import scopes_multi


def read(record: dict):
    return scopes_multi.tree_seconds(record, "hist_tree_s.multi")
