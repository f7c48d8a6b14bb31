"""Host seconds a tree of the DART cell's traced window spent in the lottery:
the summed durations of the program's `lgbm.dart_draw` spans (t draws of
upstream's stream at iteration t, a forced one where none drops), inside
`lgbm.host_inputs`, over ALL the window's trees.
Grouped in harness/scopes_dart.json; nothing where the trace has
nothing of it to read (harness/scopes_dart.py)."""

from harness import scopes_dart


def read(record: dict):
    return scopes_dart.tree_seconds(record, "dart_draw_tree_s")
