"""The names under which a training job shows in a JAX profiler trace.

One registry for both kinds.  DEVICE SCOPES are `jax.named_scope`s inside
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
`scopes_ranked.json` and `scopes_bagged.json`; tests/test_spans.py holds
their union equal).

Use the constants at the sites, never a string literal: the test greps
for `lgbm.` names outside this registry.
"""

__jax_free__ = True

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
DART_BANK = "lgbm.dart_bank"          # DART drop, normalise, bank write
# lambdarank's gradients (objectives.py), nested INSIDE lgbm.objective: a
# reader that takes the last component sees them apart, and what stays
# under lgbm.objective alone is the casts and the loop's plumbing
RANK_GATHER = "lgbm.rank_gather"      # score[doc_idx], the two row_slot gathers
RANK_SORT = "lgbm.rank_sort"          # the two argsorts, the discount look-up
RANK_PAIRS = "lgbm.rank_pairs"        # everything [QB, L, L] and its sums

DEVICE_SCOPES = (
    OBJECTIVE, GROW, HIST_ROOT, BLOCK_LIST, HIST_SWEEP, HIST_POOL,
    HIST_EXCHANGE, GAIN_SCAN, PARTITION, TREE_UPDATE, OOB_DESCENT,
    SCORE_UPDATE, VALID_UPDATE, PACK_TREE, RESORT, BAG_ARRANGE, DART_BANK,
    RANK_GATHER, RANK_SORT, RANK_PAIRS)

# -- host spans (models/gbdt.py segment loop), with their stats ------------
SEGMENT = "lgbm.segment"              # iter, k: one train_segment / iteration
HOST_INPUTS = "lgbm.host_inputs"      # plan, bagging, masks and their upload
ENQUEUE = "lgbm.enqueue"              # kind, k: the jitted executable's call;
#                                       a re-sorting one adds carried, taken,
#                                       the arrangement also window, in_bag
FLUSH = "lgbm.flush"                  # trees, bytes, exchange_bytes, blocks_swept,
#                                       grid_rows, partition_blocks,
#                                       feat_groups, block_matmuls
#                                       (a row step's feature groups and
#                                       matmuls), and the objective's own
#                                       counters (Objective.trace_counters:
#                                       lambdarank's pairs_padded, pairs_real,
#                                       queries, lmax, each what ONE tree
#                                       costs), and the sampling's:
#                                       bag_window, bag_in_bag, bag_draws
#                                       (since the last flush), feat_used,
#                                       0 where sampling is off:
#                                       _flush_pending
FLUSH_PULL = "lgbm.flush_pull"        # the device_get (host waits for device)
FLUSH_UNPACK = "lgbm.flush_unpack"    # _unpack_tree loop, stump truncation
EVAL = "lgbm.eval"                    # iter: metrics and early stopping
BAG_DRAW = "lgbm.bag_draw"            # iter, rows, in_bag: _bagging when it
#                                       redraws, INSIDE lgbm.host_inputs

HOST_SPANS = (SEGMENT, HOST_INPUTS, ENQUEUE, FLUSH, FLUSH_PULL,
              FLUSH_UNPACK, EVAL, BAG_DRAW)

# `kind` of an lgbm.enqueue span: which executable was called
ENQUEUE_KINDS = ("scan", "resort", "multi", "dart", "arrange", "general")
