"""Device seconds a tree of the traced window spent under the program's
`lgbm.hist_exchange` scope (all-reduces of histograms, the pmax of the
block-list rung), on the chip where they are most.  A collective's time
holds its wait for the slowest shard: this reads wire + skew.  Nothing
where the trace has no such scope."""

from harness import planes


def read(record: dict):
    seconds = planes.exchange_seconds(record)
    trees = record.get("window_tree_count")
    if seconds is None or not trees:
        return None
    return seconds / trees
