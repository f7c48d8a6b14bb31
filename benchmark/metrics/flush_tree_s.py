"""Host seconds a tree of the traced window spent in the program's
`lgbm.flush` spans (the pull of the pending trees and their unpacking),
summed durations.  Nothing where the trace has none of the program's
spans."""

from harness import scopes


def read(record: dict):
    return scopes.tree_seconds(record, "flush_tree_s")
