"""Host seconds a tree of the traced window cost the segment loop itself:
the summed SELF time of `lgbm.segment`, `lgbm.host_inputs` and
`lgbm.enqueue` spans (their children, the pulls among them, excluded).
Nothing where the trace has none of the program's spans."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "host_segment_tree_s")
