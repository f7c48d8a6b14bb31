"""Device seconds a tree of the traced window spent under
`lgbm.rank_gather`: the gather of the scores into the query blocks and
the two gathers that bring lambdas and hessians back to the rows.
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "rank_gather_tree_s")
