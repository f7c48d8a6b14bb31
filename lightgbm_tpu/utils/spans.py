"""The names under which a training job shows in a JAX profiler trace.

One registry for all three kinds.  DEVICE SCOPES are `jax.named_scope`s inside
the fused step: HLO metadata, so they cost nothing and end up in every
device operation's `op_name`.  The names are flat and unique, so a reader
takes the LAST `lgbm.*` component of an operation's name stack whatever
loops, conditionals and inner jits XLA puts between them.  HOST SPANS are
`jax.profiler.TraceAnnotation`s around the segment loop's host work (one
flag check with the profiler off); their keyword arguments become the
event's stats, so each count travels with its span.  Both land in the one
`.xplane.pb` of `jax.profiler.trace(dir)`, on one clock;
`benchmark/phase_table.py <dir>` prints the table (the benchmark keeps its
own copy of these names, `benchmark/harness/scopes.json`,
`scopes_ranked.json`, `scopes_bagged.json`, `scopes_dart.json`,
`scopes_multi.json` and `scopes_startup.json`; tests/test_spans.py holds
their union equal).
START-UP SPANS cover what
a job does before its steady state, where no profiler runs: `startup()`
opens the same `TraceAnnotation` AND keeps a record in this process
(name, parent, process age at the start, duration, stats), which
`startup_records()` hands to whoever asks: the start-up log line
(utils/compile_cache.py `startup_line`), `benchmark/harness/startup.py`.

Use the constants at the sites, never a string literal: the test greps
for `lgbm.` names outside this registry.
"""

__jax_free__ = True

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

# -- device scopes (models/gbdt.py step bodies, ops/grow.py) ---------------
OBJECTIVE = "lgbm.objective"          # grad_fn and the dtype casts
GROW = "lgbm.grow"                    # the grow_tree_bagged call
HIST_ROOT = "lgbm.hist_root"          # the root's full sweep and root sums
BLOCK_LIST = "lgbm.block_list"        # occupancy table, block lists' argsorts
HIST_SWEEP = "lgbm.hist_sweep"        # gh2, the bin-matrix pad, the kernel
HIST_POOL = "lgbm.hist_pool"          # parent - small, select, pool writes
HIST_EXCHANGE = "lgbm.hist_exchange"  # collectives of the sharded learners
GAIN_SCAN = "lgbm.gain_scan"          # best split of a leaf, packed
PARTITION = "lgbm.partition"          # the partition kernel, or the compare
TREE_UPDATE = "lgbm.tree_update"      # arg-max over leaves, TreeArrays writes
OOB_DESCENT = "lgbm.oob_descent"      # bag compaction's out-of-bag descent
SCORE_UPDATE = "lgbm.score_update"    # leaf-value gather-add on the scores
VALID_UPDATE = "lgbm.valid_update"    # descent and add on each valid set
PACK_TREE = "lgbm.pack_tree"          # _pack_tree
RESORT = "lgbm.resort"                # ordered-partition row re-sort
BAG_ARRANGE = "lgbm.bag_arrange"      # in-bag-first arrangement
DART_BANK = "lgbm.dart_bank"          # DART's append of a new tree's rows
# lambdarank's gradients (objectives.py), nested INSIDE lgbm.objective: a
# reader that takes the last component sees them apart, and what stays
# under lgbm.objective alone is the casts and the loop's plumbing
RANK_GATHER = "lgbm.rank_gather"      # score[doc_idx], the two row_slot gathers
RANK_SORT = "lgbm.rank_sort"          # the two argsorts, the discount look-up
RANK_PAIRS = "lgbm.rank_pairs"        # everything [QB, L, L] and its sums
# DART's score surgery (models/gbdt.py _make_fused_step_dart): lgbm.dart_bank
# above is the append alone; lgbm.dart_replay lies INSIDE the drop or the
# normalise and lgbm.dart_carry INSIDE lgbm.resort or lgbm.bag_arrange (a
# reader takes the last component)
DART_DROP = "lgbm.dart_drop"          # the dropped trees taken off the scores
DART_NORMALIZE = "lgbm.dart_normalize"  # and put back, shrunk by k / (1 + k)
DART_REPLAY = "lgbm.dart_replay"      # a dropped tree outside the leaf bank:
#                                       its leaf ids by replay_leaf_binned
DART_CARRY = "lgbm.dart_carry"        # the leaf bank's filled groups in a
#                                       re-sort or an arrangement
# the class-wise step's re-sort key (models/gbdt.py _class_key), INSIDE
# lgbm.resort: the K classes' leaf ids packed into at most two words
CLASS_KEY = "lgbm.class_key"

DEVICE_SCOPES = (
    OBJECTIVE, GROW, HIST_ROOT, BLOCK_LIST, HIST_SWEEP, HIST_POOL,
    HIST_EXCHANGE, GAIN_SCAN, PARTITION, TREE_UPDATE, OOB_DESCENT,
    SCORE_UPDATE, VALID_UPDATE, PACK_TREE, RESORT, BAG_ARRANGE, DART_BANK,
    RANK_GATHER, RANK_SORT, RANK_PAIRS, DART_DROP, DART_NORMALIZE,
    DART_REPLAY, DART_CARRY, CLASS_KEY)

# -- host spans (models/gbdt.py segment loop), with their stats ------------
SEGMENT = "lgbm.segment"              # iter, k: one train_segment / iteration
HOST_INPUTS = "lgbm.host_inputs"      # plan, bagging, masks and their upload
ENQUEUE = "lgbm.enqueue"              # kind, k: the jitted executable's call;
#                                       a re-sorting one adds carried, taken,
#                                       word_rows (the stacked matrix's), the
#                                       arrangement also window, in_bag; a
#                                       class-wise one (kind multi) classes
FLUSH = "lgbm.flush"                  # trees, bytes, exchange_bytes, blocks_swept,
#                                       grid_rows, partition_blocks,
#                                       rows_swept (the in-bag rows of
#                                       the leaves those sweeps targeted),
#                                       feat_groups, block_matmuls
#                                       (a row step's feature groups and
#                                       matmuls), and the objective's own
#                                       counters (Objective.trace_counters:
#                                       lambdarank's pairs_padded, pairs_real,
#                                       queries, lmax, each what ONE tree
#                                       costs), and the sampling's:
#                                       bag_window, bag_in_bag, bag_draws
#                                       (since the last flush), feat_used,
#                                       0 where sampling is off, and
#                                       DART's: dart_drops (trees dropped,
#                                       summed over the flushed iterations),
#                                       dart_replayed (those of them that lay
#                                       outside the leaf bank), dart_bank_rows
#                                       (trees banked), dart_bank_cap, 0 where
#                                       the job is no DART job, and the
#                                       classes': classes, class_blocks_max,
#                                       class_blocks_min (the most and the
#                                       fewest blocks_swept of one class's
#                                       flushed trees, class = tree index
#                                       mod classes), 0 in a one-class job:
#                                       _flush_pending
FLUSH_PULL = "lgbm.flush_pull"        # the device_get (host waits for device)
FLUSH_UNPACK = "lgbm.flush_unpack"    # _unpack_tree loop, stump truncation
EVAL = "lgbm.eval"                    # iter: metrics and early stopping
BAG_DRAW = "lgbm.bag_draw"            # iter, rows, in_bag: _bagging when it
#                                       redraws, INSIDE lgbm.host_inputs
DART_DRAW = "lgbm.dart_draw"          # iter, k: DART's lottery over upstream's
#                                       stream, INSIDE lgbm.host_inputs

HOST_SPANS = (SEGMENT, HOST_INPUTS, ENQUEUE, FLUSH, FLUSH_PULL,
              FLUSH_UNPACK, EVAL, BAG_DRAW, DART_DRAW)

# `kind` of an lgbm.enqueue span: which executable was called
ENQUEUE_KINDS = ("scan", "resort", "multi", "dart", "arrange", "general")

# -- start-up spans (kept as records: startup() below), with their stats ---
STARTUP_DATASET = "lgbm.startup_dataset"      # rows, features: the CLI's and
#                                               api.Dataset's load and binning
STARTUP_OBJECTIVE = "lgbm.startup_objective"  # rows, lambdarank's queries:
#                                               Objective.init
STARTUP_BOOSTER = "lgbm.startup_booster"      # rows: GBDT.__init__, DART's
#                                               (which adds bank_cap, the
#                                               trees its leaf bank holds,
#                                               and bank_bytes)
STARTUP_UPLOAD = "lgbm.startup_upload"        # bytes, shards: INSIDE the
#                                               booster's, the bin matrix,
#                                               scores and per-row state on
#                                               their way to the device
FIRST_CALL = "lgbm.first_call"                # kind, k, shards, executables,
#                                               trace_s, lower_s, backend_s,
#                                               retrieval_s, hit, again: an
#                                               lgbm.enqueue inside which
#                                               something compiled or loaded
#                                               (models/gbdt.py _enqueue); no
#                                               annotation of its own, the
#                                               enqueue span says first=1

STARTUP_SPANS = (STARTUP_DATASET, STARTUP_OBJECTIVE, STARTUP_BOOSTER,
                 STARTUP_UPLOAD, FIRST_CALL)

# moments of a job stamped once a process, as process age (stamp() below)
FIRST_DISPATCH = "first_dispatch"     # the first _enqueue: until then the
#                                       device has had no work
FIRST_TREE = "first_tree"             # the first flush that delivered trees
STAMPS = (FIRST_DISPATCH, FIRST_TREE)

STARTUP_CAP = 256       # records kept; later ones are counted only

_STAT_PATH = "/proc/self/stat"
_BOOTTIME = getattr(time, "CLOCK_BOOTTIME", None)      # Linux
_CLOCK = time.CLOCK_MONOTONIC if _BOOTTIME is None else _BOOTTIME
_started: Optional[tuple] = None    # (seconds after boot the process started,)
_records: List[Dict[str, Any]] = []
_dropped = 0
_stamps: Dict[str, Optional[float]] = {}
_local = threading.local()


def clock() -> float:
    """Seconds on the clock the records are kept on (since boot where
    the platform has CLOCK_BOOTTIME): only differences mean anything.
    Read for the records alone: no value a model depends on comes from
    it."""
    return time.clock_gettime(_CLOCK)


def _process_start() -> Optional[float]:
    """Seconds after boot at which this process started: field 22 of
    /proc/self/stat over SC_CLK_TCK (10 ms steps); None without /proc."""
    global _started
    if _started is None:
        start = None
        try:
            with open(_STAT_PATH) as fh:
                stat = fh.read()
            # the fields after the command's closing parenthesis start at 3
            ticks = int(stat[stat.rindex(")") + 2:].split()[19])
            if _BOOTTIME is not None:
                start = ticks / os.sysconf("SC_CLK_TCK")
        except (OSError, ValueError, IndexError):
            pass
        _started = (start,)
    return _started[0]


def _forget_process_start() -> None:
    global _started
    _started = None


# a forked child is another process, with a start of its own
os.register_at_fork(after_in_child=_forget_process_start)


def process_age(at: Optional[float] = None) -> Optional[float]:
    """Seconds since the process started, now or at `at` on clock();
    None where there is no /proc.  Needs no stamp handed in by whoever
    started the process."""
    start = _process_start()
    if start is None:
        return None
    return (clock() if at is None else at) - start


def open_contexts() -> list:
    """What is open on this thread, outermost first: start-up span names,
    and models/gbdt.py's open _enqueue, which pushes itself so that the
    compile ledger can hand it the executables of its call
    (`compiled_inside`)."""
    try:
        return _local.open
    except AttributeError:
        _local.open = []
        return _local.open


def record_startup(name: str, t0: float, duration: float,
                   **stats: Any) -> None:
    """Keep one record: `t0` on clock(), kept as process age; its parent
    is the start-up span open on this thread."""
    global _dropped
    if len(_records) >= STARTUP_CAP:
        _dropped += 1
        return
    parent = next((c for c in reversed(open_contexts())
                   if isinstance(c, str)), None)
    _records.append({"name": name, "parent": parent, "t0": process_age(t0),
                     "dur": duration, "stats": stats})


class startup:
    """`with spans.startup(spans.STARTUP_UPLOAD, shards=4) as stats:` a
    start-up span.  Opens the TraceAnnotation the host spans use (only
    where jax is already imported: this module imports nothing of it), so
    a job traced from its first line shows it on the device's clock, and
    keeps a record either way.  What the body learns goes into `stats`
    (`stats["bytes"] = ...`) and reaches both; `seconds` is the duration
    once it has closed.  The span ends where the host's part ends: nothing
    waits for the device."""

    __slots__ = ("name", "stats", "seconds", "_early", "_t0", "_span")

    def __init__(self, name: str, **stats: Any) -> None:
        self.name = name
        self.stats = stats
        self._early = tuple(stats)

    def __enter__(self) -> Dict[str, Any]:
        jax = sys.modules.get("jax")
        self._span = (None if jax is None else
                      jax.profiler.TraceAnnotation(self.name, **self.stats))
        if self._span is not None:
            self._span.__enter__()
        open_contexts().append(self.name)
        self._t0 = clock()
        return self.stats

    def __exit__(self, *exc: Any) -> None:
        self.seconds = clock() - self._t0
        open_contexts().pop()
        record_startup(self.name, self._t0, self.seconds, **self.stats)
        if self._span is not None:
            late = {k: v for k, v in self.stats.items()
                    if k not in self._early}
            if late:
                self._span.set_metadata(**late)
            self._span.__exit__(*exc)


def startup_records() -> List[Dict[str, Any]]:
    """The start-up records of this process so far, oldest first: dicts
    of name, parent (the start-up span open on the thread), t0 (process
    age, None without /proc), dur (seconds), stats."""
    return list(_records)


def startup_dropped() -> int:
    """Records counted but not kept: the list holds STARTUP_CAP."""
    return _dropped


def stamp(name: str) -> bool:
    """Keep the process age of this moment under `name` (one of STAMPS)
    unless it has one; -> whether this call was the first."""
    if name in _stamps:
        return False
    _stamps[name] = process_age()
    return True


def stamps() -> Dict[str, Optional[float]]:
    return dict(_stamps)
