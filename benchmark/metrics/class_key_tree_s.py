"""Device seconds a tree of the class-wise cell's traced window spent under
`lgbm.class_key`: the ten classes' leaf ids packed into the re-sort's two
key words, over ALL the window's trees.  A PART of `resort_tree_s.multi`
(`device_parts` of harness/scopes_multi.json), not beside it; 0 where
XLA fused the key into an operation of the sort's scope
(harness/scopes_multi.py)."""

from harness import scopes_multi


def read(record: dict):
    return scopes_multi.tree_seconds(record, "class_key_tree_s")
