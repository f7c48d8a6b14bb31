"""Executables that compiled in this process before the window: the
ledger's records whose `hit` is false.  0 says the run was warm.  Nothing
where the program keeps no ledger (harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.cache_misses(record)
