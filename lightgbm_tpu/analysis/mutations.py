"""Seeded-violation corpus: proof that every contract rule has power.

A static-analysis rule that has never caught a bug is a hypothesis, not
a safety net.  This module holds a corpus of DELIBERATE contract
violations — at least two per contract class — expressed as source
transforms applied to in-memory copies of the real package modules.
The harness (tests/test_graftcheck_mutations.py) asserts that

  * the UNMUTATED tree analyzes clean (no cry-wolf findings), and
  * every mutation is flagged by the expected rule, anchored on the
    expected module, with the expected evidence in the message (the
    interprocedural chain, the lock name, the drifted input kind, ...).

Transforms anchor on exact source strings and RAISE when the anchor has
drifted — a refactor that invalidates a seeded violation fails the
harness loudly instead of silently shrinking the proof corpus.

The transforms produce syntactically valid Python that would be WRONG
to run (that is the point); nothing here is ever imported or executed —
analysis is pure AST.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Tuple

from .graftlint import iter_package_files, package_root

__jax_free__ = True


def _replace_once(src: str, old: str, new: str, *, what: str) -> str:
    n = src.count(old)
    if n != 1:
        raise AssertionError(
            "mutation anchor drifted for %s: %d occurrence(s) of %r — "
            "update analysis/mutations.py alongside the refactor"
            % (what, n, old[:60]))
    return src.replace(old, new)


def _insert_after(src: str, anchor: str, addition: str, *,
                  what: str) -> str:
    return _replace_once(src, anchor, anchor + addition, what=what)


def _insert_before(src: str, anchor: str, addition: str, *,
                   what: str) -> str:
    return _replace_once(src, anchor, addition + anchor, what=what)


def _remove_decorator(src: str, prefix: str, *, what: str) -> str:
    """Remove the (possibly multi-line) decorator whose first line,
    stripped, starts with `prefix` — paren-balanced so the removal ends
    exactly where the decorator call does.  Exactly one match required."""
    lines = src.splitlines(keepends=True)
    spans = []
    i = 0
    while i < len(lines):
        if lines[i].lstrip().startswith(prefix):
            depth = 0
            j = i
            while j < len(lines):
                depth += lines[j].count("(") - lines[j].count(")")
                j += 1
                if depth <= 0:
                    break
            spans.append((i, j))
            i = j
        else:
            i += 1
    if len(spans) != 1:
        raise AssertionError(
            "mutation anchor drifted for %s: %d decorator match(es) "
            "for %r" % (what, len(spans), prefix))
    lo, hi = spans[0]
    return "".join(lines[:lo] + lines[hi:])


@dataclasses.dataclass
class Mutation:
    name: str
    contract: str          # contract class being violated
    module: str            # package-relative path the transform edits
    expect_rule: str       # rule that must flag it
    expect_path: str       # module the finding must anchor on
    expect_substr: str     # evidence that must appear in the message
    description: str
    transform: Callable[[str], str]


def _m(name: str, contract: str, module: str, expect_rule: str,
       expect_path: str, expect_substr: str, description: str,
       transform: Callable[[str], str]) -> Mutation:
    return Mutation(name, contract, module, expect_rule, expect_path,
                    expect_substr, description, transform)


# ---------------------------------------------------------------------------
# traced_pure — host syncs smuggled into the traced closure
# ---------------------------------------------------------------------------

def _t_asarray_in_grow_tree(src: str) -> str:
    return _insert_before(
        src, "    def psum(x):\n",
        "    grad = np.asarray(grad)  # seeded violation\n\n",
        what="np.asarray into grow_tree")


def _t_item_in_find_best_split(src: str) -> str:
    return _insert_after(
        src, "    dt = hist.dtype\n",
        "    _dbg = sum_g.item()  # seeded violation\n",
        what=".item() into find_best_split")


# ---------------------------------------------------------------------------
# jax_free — jax smuggled into the jax-free closure
# ---------------------------------------------------------------------------

def _t_jax_into_models_tree(src: str) -> str:
    return _insert_after(
        src, "import numpy as np\n",
        "import jax  # seeded violation\n",
        what="module-level jax into models/tree.py")


def _t_marker_off_batcher(src: str) -> str:
    return _replace_once(
        src, "\n__jax_free__ = True\n", "\n",
        what="__jax_free__ marker removal from serving/batcher.py")


def _t_jax_into_ingest_writer(src: str) -> str:
    return _insert_after(
        src, "import numpy as np\n",
        "import jax  # seeded violation\n",
        what="module-level jax into ingest/writer.py")


def _t_marker_off_ingest_shards(src: str) -> str:
    return _replace_once(
        src, "\n__jax_free__ = True\n", "\n",
        what="__jax_free__ marker removal from ingest/shards.py")


def _t_marker_off_dist(src: str) -> str:
    return _replace_once(
        src, "\n__jax_free__ = True\n", "\n",
        what="__jax_free__ marker removal from parallel/dist.py")


def _t_lazy_jax_in_get_lib(src: str) -> str:
    return _insert_after(
        src, "def get_lib() -> Optional[ctypes.CDLL]:\n",
        "    import jax  # seeded violation\n",
        what="lazy jax import into native.get_lib")


def _t_lazy_jax_in_compile_flat(src: str) -> str:
    return _insert_before(
        src, "    th, tl = split_hi_lo(thr)\n",
        "    import jax  # seeded violation\n",
        what="lazy jax import into flatforest.compile_flat")


# ---------------------------------------------------------------------------
# parity_oracle — oracle set drift + RNG/clock reach
# ---------------------------------------------------------------------------

def _t_remove_grow_oracle(src: str) -> str:
    return _remove_decorator(src, "@contract.parity_oracle(",
                             what="parity_oracle removal from grow_tree")


def _t_np_random_in_pack_tree(src: str) -> str:
    return _insert_after(
        src, "def _pack_tree(dev_tree):\n",
        "    _noise = np.random.uniform()  # seeded violation\n",
        what="np.random into _pack_tree")


# ---------------------------------------------------------------------------
# locked_by — call paths that drop the lock
# ---------------------------------------------------------------------------

def _t_unlocked_poke_in_batcher(src: str) -> str:
    return _insert_before(
        src, "    def _loop(self) -> None:\n",
        "    def poke(self) -> None:  # seeded violation\n"
        "        self._take_batch()\n\n",
        what="unlocked public poke() into MicroBatcher")


def _t_unlocked_observe_in_server(src: str) -> str:
    return _insert_after(
        src, "    def request_started(self, endpoint: str) -> None:\n",
        "        self.latency.observe(0.0)  # seeded violation\n",
        what="unlocked observe() into Metrics.request_started")


def _t_unlocked_lane_observe(src: str) -> str:
    return _insert_after(
        src, "    def request_started(self, endpoint: str) -> None:\n",
        "        self._lane_observe(\"fast\", 0.0)  # seeded violation\n",
        what="unlocked _lane_observe() into Metrics.request_started")


# ---------------------------------------------------------------------------
# fused_body — registry drift + effect-signature drift
# ---------------------------------------------------------------------------

_PLAIN_STEP_DEF = (
    "    def step(scores, valid_scores, bag_mask, fmask, bins, "
    "valid_bins,\n             gstate, stopped):\n")


def _t_remove_fused_annotation(src: str) -> str:
    # the plain maker's decorator is the only one with no extras=(...)
    return _remove_decorator(
        src, '@contract.fused_body(collectives=',
        what="fused_body removal from _make_fused_step")


def _t_rename_body_param(src: str) -> str:
    return _replace_once(
        src, _PLAIN_STEP_DEF,
        _PLAIN_STEP_DEF.replace("fmask", "feature_mask"),
        what="fmask rename in the plain fused body")


def _t_collective_drift(src: str) -> str:
    return _insert_after(
        src, _PLAIN_STEP_DEF,
        "        scores = jax.lax.ppermute(scores, 'data', [(0, 0)])"
        "  # seeded violation\n",
        what="undeclared collective into the plain fused body")


# ---------------------------------------------------------------------------
# counted_flush — transfers that dodge the accounting
# ---------------------------------------------------------------------------

def _t_rogue_device_get(src: str) -> str:
    return _insert_before(
        src,
        "        # device row slices stay unmaterialized: _flush_pending "
        "stacks\n",
        "        _probe = jax.device_get(scores)  # seeded violation\n",
        what="rogue jax.device_get into _run_fused_multi")


def _t_host_sync_in_prefetch_handoff(src: str) -> str:
    # an end-of-load device_get barrier planted right after the shard
    # windows drain: it stalls the load on every in-flight transfer
    # (defeating the async device_put pipelining the prefetch feed
    # builds) and round-trips the whole bin matrix back to the host —
    # all outside the sanctioned flush accounting
    return _insert_after(
        src,
        "        pad = self.n_pad - ds.num_data\n",
        "        parts = [jax.device_get(p) for p in parts]"
        "  # seeded violation\n",
        what="host sync into the _put_bins_streamed prefetch handoff")


def _t_remove_counted_flush(src: str) -> str:
    return _replace_once(
        src, "    @contract.counted_flush\n", "",
        what="counted_flush removal from _flush_pending")


# ---------------------------------------------------------------------------
# durable_write — binary writes that dodge the atomic helper
# ---------------------------------------------------------------------------

def _t_bare_checkpoint_write(src: str) -> str:
    return _insert_after(
        src, "        write_npz(path, arrays)\n",
        "        with open(path + '.bak', 'wb') as f:"
        "  # seeded violation\n"
        "            np.savez(f, **arrays)\n",
        what="bare open('wb') checkpoint write into save_checkpoint")


def _t_bare_sidecar_savez(src: str) -> str:
    return _insert_before(
        src, "def _rank_cache_matches(",
        "def _mirror_sidecar(path, ds):  # seeded violation\n"
        "    np.savez(path + '.rows.bak.npz', rows=ds.local_rows)\n"
        "\n\n",
        what="bare np.savez sidecar mirror into io/dataset.py")


def _t_marker_off_refresh_agent(src: str) -> str:
    return _replace_once(
        src, "\n__jax_free__ = True\n", "\n",
        what="__jax_free__ marker removal from refresh/agent.py")


def _t_bare_state_write_in_agent(src: str) -> str:
    return _insert_before(
        src, "        atomic_write_bytes(self._state_path,",
        "        with open(self._state_path, 'wb') as f:"
        "  # seeded violation\n"
        "            f.write(json.dumps(doc).encode())\n",
        what="bare open('wb') state write into the refresh agent")


# ---------------------------------------------------------------------------
# spmd_collectives — rank-divergent collective sequences (graftsync)
# ---------------------------------------------------------------------------

def _t_rank_gated_vote(src: str) -> str:
    return _replace_once(
        src,
        "        from ..parallel.dist import vote_any\n"
        "        return vote_any(flag)\n",
        "        from ..parallel.dist import vote_any\n"
        "        if self.rank == 0:  # seeded violation\n"
        "            return vote_any(flag)\n"
        "        return flag\n",
        what="rank-gated vote_any into sync_flag")


_AGREE_GATHER = (
    "        from ..parallel.dist import process_allgather\n"
    "        alls = process_allgather(\n"
    "            np.array([iteration], dtype=np.int64)).reshape(-1)\n")


def _t_branch_reordered_allgather(src: str) -> str:
    return _replace_once(
        src, _AGREE_GATHER,
        "        from ..parallel.dist import process_allgather, vote_any\n"
        "        if self.rank % 2 == 0:  # seeded violation\n"
        "            vote_any(False)\n"
        "            alls = process_allgather(\n"
        "                np.array([iteration], dtype=np.int64)"
        ").reshape(-1)\n"
        "        else:\n"
        "            alls = process_allgather(\n"
        "                np.array([iteration], dtype=np.int64)"
        ").reshape(-1)\n"
        "            vote_any(False)\n",
        what="rank-reordered allgather arms into _agree")


def _t_collective_in_rank_loop(src: str) -> str:
    return _insert_before(
        src,
        "        alls = process_allgather(\n",
        "        for _ in range(self.rank):  # seeded violation\n"
        "            process_allgather(np.zeros(1, dtype=np.int64))\n",
        what="collective inside a rank-local loop in _agree")


def _t_direct_multihost_in_write(src: str) -> str:
    return _insert_after(
        src,
        '        faultpoint("checkpoint.write")\n',
        "        from jax.experimental import multihost_utils"
        "  # seeded violation\n"
        '        multihost_utils.sync_global_devices("snapshot")\n',
        what="direct multihost_utils call into SnapshotManager.write")


# ---------------------------------------------------------------------------
# lock_order — inverted acquisition / blocking under the pool lock
# ---------------------------------------------------------------------------

def _t_inverted_lock_order(src: str) -> str:
    return _replace_once(
        src,
        "        fresh = (loader or self._load_fresh)(path)\n"
        "        with self._lock:\n"
        "            self._registered[path] = True\n",
        "        with self._lock:  # seeded violation\n"
        "            with self._load_lock:\n"
        "                fresh = (loader or self._load_fresh)(path)\n"
        "        with self._lock:\n"
        "            self._registered[path] = True\n",
        what="inverted _lock/_load_lock nesting into ModelFleet.reload")


def _t_cold_load_under_pool_lock(src: str) -> str:
    return _replace_once(
        src,
        "            fresh = self._load_fresh(path)\n"
        "            with self._lock:\n"
        "                self._pool[path] = fresh\n",
        "            with self._lock:\n"
        "                fresh = self._load_fresh(path)"
        "  # seeded violation\n"
        "                self._pool[path] = fresh\n",
        what="cold load moved under the pool lock in ModelFleet._load")


# ---------------------------------------------------------------------------
# The corpus
# ---------------------------------------------------------------------------

MUTATIONS: Tuple[Mutation, ...] = (
    _m("host-sync-in-grow-tree", "traced_pure", "ops/grow.py",
       "GC001", "ops/grow.py", "np.asarray",
       "np.asarray on the gradient inside grow_tree — a host round-trip "
       "one call below every fused body",
       _t_asarray_in_grow_tree),
    _m("item-sync-in-find-best-split", "traced_pure", "ops/split.py",
       "GC001", "ops/split.py", ".item()",
       ".item() on a leaf total inside find_best_split — a host sync "
       "several calls below the traced entry points",
       _t_item_in_find_best_split),

    _m("jax-into-models-tree", "jax_free", "models/tree.py",
       "GC002", "serving/server.py",
       "serving/forest.py -> models/tree.py",
       "module-level `import jax` in models/tree.py — reaches "
       "serving/server.py two import hops up the jax-free tree",
       _t_jax_into_models_tree),
    _m("marker-removed-from-batcher", "jax_free", "serving/batcher.py",
       "GC007", "serving/batcher.py", "__jax_free__",
       "deleting the __jax_free__ declaration from a serving module — "
       "modules under DECLARE_DIRS cannot opt out silently",
       _t_marker_off_batcher),
    _m("lazy-jax-in-native-get-lib", "jax_free", "native/__init__.py",
       "GC002", "native/__init__.py", "lazy jax import",
       "a lazy `import jax` inside native.get_lib — reached from the "
       "@contract.jax_free fast-predict / serving fallback closures",
       _t_lazy_jax_in_get_lib),
    _m("lazy-jax-in-compile-flat", "jax_free", "serving/flatforest.py",
       "GC002", "serving/flatforest.py", "lazy jax import",
       "a lazy `import jax` inside the flat-table compiler — "
       "compile_flat runs in warm() on the low-latency lane of a "
       "backend=native process and is @contract.jax_free",
       _t_lazy_jax_in_compile_flat),

    _m("jax-into-ingest-writer", "jax_free", "ingest/writer.py",
       "GC002", "ingest/writer.py", "jax",
       "module-level `import jax` in the ingest bin-pass — the "
       "parse/shard-write path must stay importable (and fork-safe) "
       "in jax-free lanes: CLI task=ingest, parse worker processes",
       _t_jax_into_ingest_writer),
    _m("marker-removed-from-ingest-shards", "jax_free",
       "ingest/shards.py", "GC007", "ingest/shards.py",
       "pinned jax-free",
       "deleting the __jax_free__ declaration from a module PINNED by "
       "EXPECTED_JAX_FREE under the new ingest/ tree",
       _t_marker_off_ingest_shards),

    _m("pinned-marker-removed-from-dist", "jax_free",
       "parallel/dist.py", "GC007", "parallel/dist.py",
       "pinned jax-free",
       "deleting the marker from a module PINNED by EXPECTED_JAX_FREE "
       "— the registry, not just the directory rule, must flag it",
       _t_marker_off_dist),

    _m("oracle-annotation-removed", "parity_oracle", "ops/grow.py",
       "GC003", "ops/grow.py", "missing its @contract.parity_oracle",
       "removing grow_tree's parity_oracle annotation — the oracle SET "
       "is pinned by EXPECTED_PARITY_ORACLES",
       _t_remove_grow_oracle),
    _m("np-random-in-pack-tree", "parity_oracle", "models/gbdt.py",
       "GC003", "models/gbdt.py", "np.random",
       "np.random inside _pack_tree — reachable from the general-path "
       "parity oracle (GBDT._train_tree)",
       _t_np_random_in_pack_tree),

    _m("unlocked-poke-into-batcher", "locked_by", "serving/batcher.py",
       "GC004", "serving/batcher.py", "without holding",
       "a public MicroBatcher method calling _take_batch without "
       "holding _cv",
       _t_unlocked_poke_in_batcher),
    _m("unlocked-observe-in-server", "locked_by", "serving/server.py",
       "GC004", "serving/server.py", "Metrics.request_started",
       "Metrics.request_started calling _Histogram.observe outside "
       "`with self._lock`",
       _t_unlocked_observe_in_server),
    _m("unlocked-lane-observe-in-server", "locked_by",
       "serving/server.py", "GC004", "serving/server.py",
       "_lane_observe",
       "Metrics.request_started calling the per-lane latency recorder "
       "outside `with self._lock` — the lane counters and histograms "
       "share the metrics lock",
       _t_unlocked_lane_observe),

    _m("fused-annotation-removed", "fused_body", "models/gbdt.py",
       "GC005", "models/gbdt.py", "missing its @contract.fused_body",
       "removing _make_fused_step's fused_body annotation — the maker "
       "SET is pinned by EXPECTED_FUSED_BODIES",
       _t_remove_fused_annotation),
    _m("body-param-renamed", "fused_body", "models/gbdt.py",
       "GC005", "models/gbdt.py", "does not consume the uniform core",
       "renaming the plain body's fmask parameter — effect-signature "
       "drift between the six bodies",
       _t_rename_body_param),
    _m("collective-drift-in-plain-body", "fused_body", "models/gbdt.py",
       "GC005", "models/gbdt.py", "ppermute",
       "an undeclared collective in ONE body — the uniform collective "
       "signature across the six bodies breaks",
       _t_collective_drift),

    _m("rogue-device-get", "counted_flush", "models/gbdt.py",
       "GC006", "models/gbdt.py", "GBDT._run_fused_multi",
       "a jax.device_get outside the counted flush — bench's "
       "device_gets_per_100_trees would silently under-count",
       _t_rogue_device_get),
    _m("counted-flush-annotation-removed", "counted_flush",
       "models/gbdt.py", "GC006", "models/gbdt.py",
       "GBDT._flush_pending",
       "removing the counted_flush annotation — the flush's own "
       "device_get immediately loses its sanction",
       _t_remove_counted_flush),
    _m("host-sync-in-prefetch-handoff", "counted_flush",
       "models/gbdt.py", "GC006", "models/gbdt.py",
       "GBDT._put_bins_streamed",
       "a jax.device_get barrier planted at the end of the shard-"
       "window prefetch handoff — it stalls the load on every "
       "in-flight transfer, round-trips the bin matrix to the host, "
       "and dodges the flush accounting",
       _t_host_sync_in_prefetch_handoff),

    _m("bare-checkpoint-write", "durable_write", "models/gbdt.py",
       "GC008", "models/gbdt.py", "open(.., 'wb')",
       "a bare open('wb') checkpoint copy next to the atomic write — "
       "a crash mid-write truncates it in place and poisons the next "
       "resume",
       _t_bare_checkpoint_write),
    _m("bare-sidecar-savez", "durable_write", "io/dataset.py",
       "GC008", "io/dataset.py", "np.savez",
       "a bare np.savez of the rows sidecar outside the atomic helper "
       "— a truncated sidecar desyncs the cluster's row partition",
       _t_bare_sidecar_savez),

    _m("marker-removed-from-refresh-agent", "jax_free",
       "refresh/agent.py", "GC007", "refresh/agent.py",
       "pinned jax-free",
       "deleting the __jax_free__ declaration from the deploy agent — "
       "bypassing the EXPECTED_JAX_FREE registry would let a jax "
       "import tax every refresh cycle with a backend init",
       _t_marker_off_refresh_agent),
    _m("bare-state-write-in-agent", "durable_write",
       "refresh/agent.py", "GC008", "refresh/agent.py",
       "open(.., 'wb')",
       "a bare open('wb') of the agent's durable state file — a crash "
       "mid-write truncates the consumed-drops ledger and the rerun "
       "double-trains or skips data",
       _t_bare_state_write_in_agent),

    _m("rank-gated-vote-any", "spmd_collectives",
       "resilience/snapshot.py", "GC009", "resilience/snapshot.py",
       "vote_any",
       "vote_any behind `if self.rank == 0` in sync_flag — rank 0 "
       "enters the collective alone and blocks until the deadline",
       _t_rank_gated_vote),
    _m("branch-reordered-allgather", "spmd_collectives",
       "resilience/snapshot.py", "GC009", "resilience/snapshot.py",
       "different collective sequences",
       "the SAME collective set in a different ORDER per rank parity "
       "— the sequence-sensitive check catches what a set comparison "
       "(GC005-style) cannot",
       _t_branch_reordered_allgather),
    _m("collective-in-rank-local-loop", "spmd_collectives",
       "resilience/snapshot.py", "GC010", "resilience/snapshot.py",
       "range(self.rank)",
       "an allgather inside `for _ in range(self.rank)` — every rank "
       "runs a different collective count and the pool wedges",
       _t_collective_in_rank_loop),
    _m("direct-multihost-in-snapshot", "spmd_collectives",
       "resilience/snapshot.py", "GC011", "resilience/snapshot.py",
       "multihost_utils",
       "a bare multihost_utils call in SnapshotManager.write — it "
       "bypasses dist.py, so no deadline wrapping and no trace",
       _t_direct_multihost_in_write),

    _m("inverted-lock-order-in-fleet", "lock_order",
       "serving/fleet.py", "GC012", "serving/fleet.py", "cycle",
       "reload nests _load_lock under _lock while _load nests _lock "
       "under _load_lock — a deadlock window between /reload and a "
       "cold-miss request",
       _t_inverted_lock_order),
    _m("cold-load-under-pool-lock", "lock_order",
       "serving/fleet.py", "GC012", "serving/fleet.py", "_load_fresh",
       "the cold parse+warm moved under the POOL lock — every warm "
       "hit stalls behind a multi-second model load (the discipline "
       "fleet.py's comments used to carry, now machine-checked)",
       _t_cold_load_under_pool_lock),
)


def base_sources(root: str = "") -> Dict[str, str]:
    """{package-relative path: source} for the real tree."""
    root = root or package_root()
    out: Dict[str, str] = {}
    for path in iter_package_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, "r", encoding="utf-8") as f:
            out[rel] = f.read()
    return out


def apply_mutation(sources: Dict[str, str],
                   mutation: Mutation) -> Dict[str, str]:
    """A mutated copy of `sources`; raises if the anchor drifted or the
    transform was a no-op."""
    if mutation.module not in sources:
        raise AssertionError("mutation %s targets missing module %s"
                             % (mutation.name, mutation.module))
    mutated = dict(sources)
    new_src = mutation.transform(sources[mutation.module])
    if new_src == sources[mutation.module]:
        raise AssertionError("mutation %s was a no-op" % mutation.name)
    mutated[mutation.module] = new_src
    return mutated


def contract_classes() -> List[str]:
    return sorted({m.contract for m in MUTATIONS})
