"""The work DART's score surgery needs, counted once, whatever implements it.

A dropped tree is taken off the scores before the gradients and put back,
shrunk, after the new tree: two passes over the rows a dropped tree, each of
which has to read the row's leaf id in that tree (1 B where a tree has at
most 256 leaves) and read and write its score (4 B each): 9 B a row and pass.
Whether the id comes from a bank or from a replay of the tree's splits is the
implementation's business; the least time is the bytes at the chip's peak
(`peaks.json`).  A tree's growth is `work.py`'s, unchanged by the dropping.
"""

from __future__ import annotations

from harness import work

PASS_BYTES_A_ROW = 1 + 4 + 4      # leaf id, score read, score written
PASSES_A_DROP = 2                 # the drop and the normalise


def surgery_bytes(drops: int, rows: int) -> int:
    return PASSES_A_DROP * int(drops) * int(rows) * PASS_BYTES_A_ROW


def surgery_least_seconds(record: dict, drops: int) -> float:
    """The least time of `drops` dropped trees' two passes over the run's
    rows."""
    return (surgery_bytes(drops, record["in_bag_rows"])
            / work.peaks(record["device_kind"])["bytes_per_s"])
