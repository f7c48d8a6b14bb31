"""Device seconds a tree of the traced window spent on histograms: the
root sweep, the block list, the sweeps (kernel, the bin matrix's pad,
gh2), the pool arithmetic and the exchange; the scopes are grouped in
harness/scopes.json.  Nothing where the trace has no `lgbm.*` scope."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "hist_tree_s")
