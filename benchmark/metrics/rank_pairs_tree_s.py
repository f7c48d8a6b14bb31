"""Device seconds a tree of the traced window spent under
`lgbm.rank_pairs`: everything [queries, L, L] of lambdarank's pair pass
and its sums.
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "rank_pairs_tree_s")
