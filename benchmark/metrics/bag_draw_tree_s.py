"""Host seconds a tree of the bagged cell's traced window spent drawing bags:
the summed durations of the program's `lgbm.bag_draw` spans (the body of
`GBDT._bagging` when it redraws: the walk over upstream's stream into the
new mask) over ALL the window's trees.  Hidden where the device is busy
meanwhile; `device_idle_pct.bag` says how far it is not.
Grouped in harness/scopes_bagged.json; nothing where the trace has
nothing of it to read (harness/scopes_bagged.py)."""

from harness import scopes_bagged


def read(record: dict):
    return scopes_bagged.tree_seconds(record, "bag_draw_tree_s")
