"""Trees dropped a tree of the DART cell's traced window: the program's own
counter `dart_drops` (stats of the window's `lgbm.flush` spans, the sum of k
over the flushed iterations) over the window's trees.  Nothing where the
program carries no such counter."""

from harness import scopes_dart


def read(record: dict):
    c = scopes_dart.flush_counters(record)
    trees = record.get("window_tree_count")
    if not c or not trees:
        return None
    return c["dart_drops"] / trees
