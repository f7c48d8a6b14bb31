"""`backend_s` + `retrieval_s` (the compile proper or the persistent
cache's load) of the ledger's records before the window whose context is
no `lgbm.enqueue`: the executables outside the training steps (the
objective's jits, eager pads and casts, the score pull's `argsort`).  Nothing
where the program keeps no ledger (harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.ledger_seconds(record, "startup_other_compile_s")
