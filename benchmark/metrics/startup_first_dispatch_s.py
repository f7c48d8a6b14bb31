"""Process age at the program's first `lgbm.enqueue` (its stamp
`first_dispatch`, `utils/spans.py`): all a job pays before the device gets
work.  Nothing where the program keeps no start-up records
(harness/startup.py)."""

from harness import startup


def read(record: dict):
    return startup.first_dispatch(record)
