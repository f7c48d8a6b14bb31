"""`device_unscoped_pct` of the DART cell: device operation time under no
`lgbm.*` scope over all operation time in the traced window, in percent.
With the ten device groups of harness/scopes_dart.json it covers every
device operation once."""

from harness import scopes, scopes_dart


def read(record: dict):
    red = scopes_dart.for_record(record)
    return None if red is None else scopes.unscoped_pct(red)
