"""Device seconds a tree of the traced window spent under `lgbm.rank_sort`:
the two argsorts of every query block and the discount look-up.
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "rank_sort_tree_s")
