"""The share of the static compacted window that in-bag rows fill, in
percent: 100 x `bag_in_bag` / `bag_window`, the program's own counters
(stats of the traced window's `lgbm.flush` spans).  The window is the bag
padded to a row unit, so a reading far under 100 says the sweeps run over
rows no tree uses.  Nothing where the program carries no such counter or
compaction is off (a window of 0)."""

from harness import scopes_bagged


def read(record: dict):
    c = scopes_bagged.flush_counters(record)
    if not c or not c["bag_window"]:
        return None
    return 100.0 * c["bag_in_bag"] / c["bag_window"]
