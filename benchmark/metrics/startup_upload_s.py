"""Host seconds in the program's `lgbm.startup_upload` spans (inside the
booster's: the bin matrix and the scores handed to the device) before the
window, summed.  The span ends where the host's part ends: the transfers
are asynchronous and nothing waits for them.  Nothing where the program
keeps no start-up records (harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.span_seconds(record, "startup_upload_s")
