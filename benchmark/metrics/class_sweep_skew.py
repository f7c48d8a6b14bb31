"""How unevenly the one shared row order serves the classes: the row blocks
that the class whose trees swept the most visited over those of the class
that swept the fewest, summed over the traced window's flushes (the
program's `class_blocks_max` / `class_blocks_min` on `lgbm.flush`; a
class is the tree's index mod the classes).  1 is every class's sweeps
alike; nothing where no flush carries the counters
(harness/scopes_multi.py)."""

from harness import scopes_multi


def read(record: dict):
    c = scopes_multi.flush_counters(record)
    if not c or not c["class_blocks_min"]:
        return None
    return c["class_blocks_max"] / c["class_blocks_min"]
