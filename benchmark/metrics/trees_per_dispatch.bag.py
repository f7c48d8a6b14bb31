"""Trees the bagged cell's window grew over the dispatches it made
(`dispatch_count()`): the arrangements count, so a period of 15 trees is
4 + 3 dispatches."""

def read(record: dict):
    if not record.get("dispatches"):
        return None
    return record["window_tree_count"] / record["dispatches"]
