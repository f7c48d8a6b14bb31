"""`startup_first_dispatch_s` less `startup_objective_s` and
`startup_booster_s`: the seconds before the device gets work that lie under
no start-up span of the program (the interpreter, imports, device
discovery, the harness's rows).  The coverage number of the start-up, as
`device_unscoped_pct` is the step's.  Never negative; nothing where the
program keeps no start-up records (harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.unspanned(record)
