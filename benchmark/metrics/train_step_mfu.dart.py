"""The DART step's share of the chip's peaks: the least time the chip could
take for the window's trees (harness/work.py, peaks.json) PLUS the least time
of the window's drop and normalise passes (harness/work_dart.py, from the
program's `dart_drops`) over the traced window, in percent."""

from harness import scopes_dart, work, work_dart


def read(record: dict):
    tr = record.get("trace")
    c = scopes_dart.flush_counters(record)
    if (not tr or not tr.get("window_s") or not record.get("window_trees")
            or not c):
        return None
    least = (work.window_least_seconds(record)
             + work_dart.surgery_least_seconds(record, c["dart_drops"]))
    return 100.0 * least / tr["window_s"]
