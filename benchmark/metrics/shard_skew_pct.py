"""How unevenly the shards work: (most - least over the chips) of device
seconds under the histogram scopes a shard works through alone (root
sweep, block list, sweeps, pool: harness/scopes.json's `hist_tree_s` less
the exchange), over the most, in percent.  Nothing on one chip or where
the trace has no `lgbm.*` scope."""

from harness import planes


def read(record: dict):
    red = planes.for_record(record)
    if red is None or len(red["planes"]) < 2:
        return None
    own = planes.group_seconds(red, planes.OWN_HIST).values()
    if max(own) <= 0.0:
        return None
    return 100.0 * (max(own) - min(own)) / max(own)
