"""`device_unscoped_pct` of a ranking cell: device operation time under no
`lgbm.*` scope over all operation time in the traced window, in percent.
With the nine `*_tree_s` metrics of harness/scopes_ranked.json it covers
every device operation once."""

from harness import scopes


def read(record: dict):
    red = scopes.for_record(record)
    return None if red is None else scopes.unscoped_pct(red)
