"""The pair pass's share of its roofline: the least time the vector unit
needs for one tree's pairs (harness/work_ranked.py: sum of L^2 over the
run's own queries x the operations a cell costs by the reference's
formula, over harness/peaks_vector.json) over the device seconds a tree
spent under `lgbm.rank_pairs`, in percent.  Padded cells are no work, so
padding lowers it.  Bound by operations."""

from harness import scopes_ranked, work_ranked


def read(record: dict):
    seconds = scopes_ranked.tree_seconds(record, "rank_pairs_tree_s")
    if not seconds or record.get("query_lengths") is None:
        return None
    return 100.0 * work_ranked.tree_least_seconds(
        record["query_lengths"], record["device_kind"]) / seconds
