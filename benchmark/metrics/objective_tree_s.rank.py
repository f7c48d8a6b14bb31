"""Device seconds a tree of a ranking cell's traced window spent under
`lgbm.objective` in NO deeper scope: the casts, the query blocks' scan and
its copies.  The objective's whole is this and `rank_gather_tree_s`,
`rank_sort_tree_s`, `rank_pairs_tree_s`.
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "objective_tree_s.rank")
