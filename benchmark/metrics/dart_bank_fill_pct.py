"""The share of the leaf bank that banked trees fill at the traced window's
last flush, in percent: 100 x `dart_bank_rows` / `dart_bank_cap`, the
program's own counters.  At 100 every later tree is replayed where it is
dropped.  Nothing where the program carries no such counter."""

from harness import scopes_dart


def read(record: dict):
    c = scopes_dart.flush_counters(record)
    if not c or not c["dart_bank_cap"]:
        return None
    return 100.0 * c["dart_bank_rows"] / c["dart_bank_cap"]
