"""Host seconds in the program's `lgbm.startup_booster` spans (the whole
of `GBDT.__init__`, the upload included) before the window, summed.  Nothing
where the program keeps no start-up records (harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.span_seconds(record, "startup_booster_s")
