"""Device operation time under no `lgbm.*` scope over all operation time
in the traced window, in percent: what the phase metrics do not cover.
Nothing where the trace has no `lgbm.*` scope at all."""

from harness import scopes


def read(record: dict):
    red = scopes.for_record(record)
    return None if red is None else scopes.unscoped_pct(red)
