"""The drop and normalise passes' share of the chip's memory peak: the least
time of the traced window's passes (harness/work_dart.py: 2 x `dart_drops` x
rows x 9 B, a byte of leaf id and a score read and written, at peaks.json's
bytes a second) over the device seconds under `lgbm.dart_drop` +
`lgbm.dart_normalize`, in percent.  A replayed tree's descent is no part of
the seconds (`dart_replay_tree_s`).  Nothing where the program carries no
such counter or the trace no such scope."""

from harness import scopes_dart, work_dart


def read(record: dict):
    c = scopes_dart.flush_counters(record)
    trees = record.get("window_tree_count")
    parts = [scopes_dart.tree_seconds(record, m)
             for m in ("dart_drop_tree_s", "dart_normalize_tree_s")]
    if not c or not trees or None in parts or sum(parts) <= 0.0:
        return None
    return (100.0 * work_dart.surgery_least_seconds(record, c["dart_drops"])
            / (sum(parts) * trees))
