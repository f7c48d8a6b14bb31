"""How many times more row blocks the traced window's block-list sweeps
visited than their rows would fill: blocks_swept x 8192 / rows_swept, the
program's own counters (stats of the window's `lgbm.flush` spans, summed:
the occupied row blocks of every sweep, root included and summed over
shards, and the in-bag rows of the leaves those sweeps targeted).  1 is
every swept leaf packed into whole blocks; the row order that the re-sort
leaves decides the rest.  Nothing for an untraced run, or where no flush
counts rows (a program without the counter; a stat of value 0 reads as
absent)."""

from harness import scopes

ROW_BLOCK = 8192        # rows a block of the sweep kernel's grid


def read(record: dict):
    red = scopes.for_record(record)
    if red is None:
        return None
    flushes = [s.stats for s in red["spans_in_window"]
               if s.name == "lgbm.flush"]
    rows = sum(int(s.get("rows_swept", 0)) for s in flushes)
    if not rows:
        return None
    blocks = sum(int(s.get("blocks_swept", 0)) for s in flushes)
    return blocks * ROW_BLOCK / rows
