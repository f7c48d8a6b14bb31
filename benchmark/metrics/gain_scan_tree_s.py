"""Device seconds a tree of the traced window spent under `lgbm.gain_scan`:
the best split of every new leaf, gated and packed.  Nothing where the
trace has no `lgbm.*` scope (harness/scopes.py)."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "gain_scan_tree_s")
