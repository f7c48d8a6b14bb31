"""Device seconds a tree of the bagged cell's traced window spent under
`lgbm.bag_arrange`: the in-bag-first arrangement after a redraw (a stable
sort by the bag's bit, the gather of the stacked words, the gather of the
bins), over ALL the window's trees: its amortised cost.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "bag_arrange_tree_s")
