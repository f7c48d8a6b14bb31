"""A ranking step's share of the chip's peaks: the least time the chip could
take for the window's trees (harness/work.py at the cell's F, peaks.json)
PLUS the least time of their pair passes (harness/work_ranked.py,
peaks_vector.json), over the traced window, in percent."""

from harness import work, work_ranked


def read(record: dict):
    tr = record.get("trace")
    if (not tr or not tr.get("window_s") or not record.get("window_trees")
            or record.get("query_lengths") is None):
        return None
    pairs = work_ranked.tree_least_seconds(record["query_lengths"],
                                           record["device_kind"])
    least = (work.window_least_seconds(record)
             + len(record["window_trees"]) * pairs)
    return 100.0 * least / tr["window_s"]
