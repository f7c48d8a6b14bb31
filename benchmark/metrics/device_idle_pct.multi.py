"""1 - the union of the device's operation intervals over the traced window
of the class-wise cell, in percent: what the host's flush and waits leave
the chip without work."""

def read(record: dict):
    tr = record.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
