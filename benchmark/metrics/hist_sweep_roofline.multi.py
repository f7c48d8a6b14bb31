"""`hist_sweep_roofline` of the class-wise cell: the least time for the
window's trees (harness/work.py at F = 784, peaks.json) over the summed
device time of the trace's `%leaf_histogram*` events, in percent.  Nothing
where the trace shows no such event."""

from harness import trace, work


def read(record: dict):
    tr = record.get("trace")
    if not tr or not record.get("window_trees"):
        return None
    seconds = trace.kernel_seconds(tr["events"], ["%leaf_histogram"])
    if seconds <= 0.0:
        return None
    return 100.0 * work.window_least_seconds(record) / seconds
