"""The sharded step's share of the chips' peak: the least time for the
window's trees, the work counted over ALL rows (harness/work.py,
peaks.json) and divided by the chips that share it, over the traced
window, in percent."""

from harness import work


def read(record: dict):
    tr = record.get("trace")
    if (not tr or not tr.get("window_s") or not record.get("window_trees")
            or not record.get("shards")):
        return None
    least = work.window_least_seconds(record) / record["shards"]
    return 100.0 * least / tr["window_s"]
