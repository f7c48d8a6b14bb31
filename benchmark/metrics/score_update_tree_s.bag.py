"""`score_update_tree_s` of the bagged cell: device seconds a tree spent
under `lgbm.score_update`, `valid_update`, `pack_tree` and `dart_bank`; the
update is over ALL rows.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "score_update_tree_s.bag")
