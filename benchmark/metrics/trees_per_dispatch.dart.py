"""Trees the DART cell's window grew over the dispatches it made
(`dispatch_count()`): 16 / 3 a period."""

def read(record: dict):
    if not record.get("dispatches"):
        return None
    return record["window_tree_count"] / record["dispatches"]
