"""`resort_tree_s` of the class-wise cell: device seconds a tree spent under
lgbm.resort WITH the class key (lgbm.class_key, also read alone as class_key_tree_s), over ALL the traced window's trees (the amortised cost).
Grouped in harness/scopes_multi.json; nothing where the trace has
nothing of it to read (harness/scopes_multi.py)."""

from harness import scopes_multi


def read(record: dict):
    return scopes_multi.tree_seconds(record, "resort_tree_s.multi")
