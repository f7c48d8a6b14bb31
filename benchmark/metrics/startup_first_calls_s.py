"""Summed seconds of the program's `lgbm.first_call` records before the
window: the `lgbm.enqueue` calls inside which an executable was traced,
lowered, compiled or loaded (on a cold job the compile; on a warm one the
Python tracing and lowering and the cache's load).  The device may be busy
meanwhile with the call before.  Nothing where the program keeps no
start-up records (harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.span_seconds(record, "startup_first_calls_s")
