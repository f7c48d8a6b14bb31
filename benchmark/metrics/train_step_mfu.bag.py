"""The bagged step's share of the chip's peaks: the least time the chip could
take for the window's trees (harness/work.py with the visits counted over
IN-BAG rows, peaks.json) over the traced window, in percent.  The draws,
the arrangements and the out-of-bag descent are no part of the least time:
they lower the share."""

from harness import work


def read(record: dict):
    tr = record.get("trace")
    if not tr or not tr.get("window_s") or not record.get("window_trees"):
        return None
    return 100.0 * work.window_least_seconds(record) / tr["window_s"]
