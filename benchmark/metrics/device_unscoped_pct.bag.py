"""`device_unscoped_pct` of the bagged cell: device operation time under no
`lgbm.*` scope over all operation time in the traced window, in percent.
With the eight device `*_tree_s` metrics of harness/scopes_bagged.json it
covers every device operation once."""

from harness import scopes, scopes_bagged


def read(record: dict):
    red = scopes_bagged.for_record(record)
    return None if red is None else scopes.unscoped_pct(red)
