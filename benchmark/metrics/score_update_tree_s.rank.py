"""`score_update_tree_s` of a ranking cell: device seconds a tree of the
traced window spent under `lgbm.score_update`, `valid_update`,
`pack_tree`, `dart_bank`.
Grouped in harness/scopes_ranked.json; nothing where the trace has
nothing of it to read (harness/scopes_ranked.py)."""

from harness import scopes_ranked


def read(record: dict):
    return scopes_ranked.tree_seconds(record, "score_update_tree_s.rank")
