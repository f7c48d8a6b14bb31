"""Host seconds in the program's `lgbm.startup_objective` spans
(`Objective.init`: the label-derived state, lambdarank's query blocks, and
their upload) before the window, summed.  Nothing where the program keeps
no start-up records (harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.span_seconds(record, "startup_objective_s")
