"""The share of the pair pass's cells that are padding, in percent: 100 x
(1 - pairs_real / pairs_padded), the program's own counters (stats of the
traced window's `lgbm.flush` spans: the cells the padded query blocks
evaluate a tree against the sum of L^2 over the queries).  Nothing where
the program carries no such counter."""

from harness import scopes_ranked


def read(record: dict):
    c = scopes_ranked.flush_counters(record)
    if not c or not c["pairs_padded"]:
        return None
    return 100.0 * (1.0 - c["pairs_real"] / c["pairs_padded"])
