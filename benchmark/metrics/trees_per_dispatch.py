"""Trees the window grew over the dispatches it made (`dispatch_count()`)."""


def read(record: dict):
    if not record.get("dispatches"):
        return None
    return record["window_tree_count"] / record["dispatches"]
