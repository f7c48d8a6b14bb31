"""`host_segment_tree_s` of the DART cell: the summed SELF time of
`lgbm.segment`, `lgbm.host_inputs` and `lgbm.enqueue` spans in the traced
window over its trees; the lottery, a span of its own inside
`lgbm.host_inputs`, is not in it (`dart_draw_tree_s`).
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "host_segment_tree_s.dart")
