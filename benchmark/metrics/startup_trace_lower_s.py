"""`trace_s` + `lower_s` of every record of the program's compile ledger
(`utils/compile_cache.py`) before the window: Python tracing and lowering
to MLIR, paid warm or cold.  Nothing where the program keeps no ledger
(harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.ledger_seconds(record, "startup_trace_lower_s")
