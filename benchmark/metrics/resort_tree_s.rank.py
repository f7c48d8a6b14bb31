"""`resort_tree_s` of a ranking cell: device seconds of the re-sort over ALL
the traced window's trees, the objective's `rebuild` (an argsort of the
permutation and the gather that remaps the query blocks' row positions)
included.
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "resort_tree_s.rank")
