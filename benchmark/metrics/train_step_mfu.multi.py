"""The class-wise step's share of the chip's peaks: the least time the chip
could take for the window's trees (harness/work.py, peaks.json; the
softmax and the re-sort need no work of their own in that count) over the
traced window, in percent."""

from harness import work


def read(record: dict):
    tr = record.get("trace")
    if not tr or not tr.get("window_s") or not record.get("window_trees"):
        return None
    return 100.0 * work.window_least_seconds(record) / tr["window_s"]
