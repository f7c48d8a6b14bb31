"""The idlest chip's idle share of the traced window: 1 - the union of
that chip's operation intervals over the window, in percent."""


def read(record: dict):
    tr = record.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
