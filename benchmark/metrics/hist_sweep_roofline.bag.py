"""`hist_sweep_roofline` of the bagged cell: the least time for the window's
trees over the summed device time of the trace's `%leaf_histogram*`
events, in percent.  The work (harness/work.py) is counted over IN-BAG
rows: the root's visit is the bag (`in_bag_rows` of the record) and a
split's the smaller child's in-bag rows (a tree's leaf counts are in-bag
counts under bagging); the same peaks.  Nothing where the trace shows no
such event."""

from harness import trace, work


def read(record: dict):
    tr = record.get("trace")
    if not tr or not record.get("window_trees"):
        return None
    seconds = trace.kernel_seconds(tr["events"], ["%leaf_histogram"])
    if seconds <= 0.0:
        return None
    return 100.0 * work.window_least_seconds(record) / seconds
