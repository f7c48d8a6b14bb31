"""`host_segment_tree_s` of the bagged cell: the summed SELF time of
`lgbm.segment`, `lgbm.host_inputs` and `lgbm.enqueue` spans in the traced
window over its trees; the draw, a span of its own inside
`lgbm.host_inputs`, is not in it (`bag_draw_tree_s`).
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "host_segment_tree_s.bag")
