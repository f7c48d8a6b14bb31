"""graftcheck contract registry — invariants declared at the definition
site, verified whole-program by analysis/graftcheck.py.

graftlint (graftlint.py) checks invariants it can see from ONE module's
AST.  The contracts here carry the invariants that are only meaningful
across modules: a fused step body must stay trace-pure through every
helper it calls (ops/grow.py, ops/predict.py, ...), a jax-free module
must stay jax-free through its whole import closure, a serving mutator
is only correct if every call path into it holds the lock.  Each
decorator is a ZERO-COST runtime no-op (it tags and returns the
function unchanged — stdlib only, safe in jax-free modules and on hot
paths); the analyzer reads the decoration from the AST, so the checks
run without importing the annotated code.

Contract classes (checking rules live in graftcheck.py):

  @contract.traced_pure
      This function (and, for factories, the closures it returns) is
      device code: nothing it TRANSITIVELY calls inside the package may
      host-sync (np.asarray/np.array, jax.device_get/put, .item(),
      .block_until_ready()).  Rule GC001.

  @contract.parity_oracle("why this path is the oracle")
      This function is a bit-parity oracle (PARITY.md): the K=1 /
      masked / general paths other configurations are tested against.
      Nothing it transitively calls may read the clock or any RNG
      outside utils/mt19937, and the set of oracles is pinned by
      EXPECTED_PARITY_ORACLES — removing or renaming an annotation is
      itself a finding.  Rule GC003.

  @contract.jax_free
      This function must be callable without jax entering sys.modules:
      nothing it transitively calls may import jax, not even lazily
      inside a function body.  (Module-granular jax-freedom is declared
      with a module-level `__jax_free__ = True` marker instead — see
      below.)  Rule GC002.

  @contract.locked_by("_lock")
      Every self.* store in this function is protected by the named
      lock, which the CALLER holds: the analyzer verifies every package
      call path into the function lexically holds a `with <...name>:`
      (or passes through another function with the same contract), and
      graftlint GL006 stops demanding per-line suppressions inside it.
      Rule GC004.

  @contract.fused_body(extras=(...), collectives=(...))
      This step MAKER builds one of the fused training-step bodies
      (models/gbdt.py).  The analyzer resolves the maker to its body
      closure(s) through the call graph and verifies the body's EFFECT
      SIGNATURE: it consumes exactly the FUSED_CORE inputs plus the
      declared extras (parameter names normalized via CONSUME_KINDS),
      its transitive collective set equals the declared one, and every
      maker declares the SAME collectives — so any drift between the
      six bodies that would break the planned composable fused-step
      builder (ROADMAP) is a lint error today.  The full maker set is
      pinned by EXPECTED_FUSED_BODIES.  Rule GC005.

  @contract.counted_flush
      This function is a sanctioned deferred-flush site: the ONLY place
      allowed to call jax.device_get, so analysis/guards.py transfer
      accounting (bench's device_gets_per_100_trees) cannot silently
      under-count when a new code path materializes device buffers.
      Rule GC006.

  @contract.durable_write
      This function is a sanctioned durable-artifact writer: binary
      writes (`open(.., "wb"/"ab")`, np.savez) are only legal inside a
      function carrying this contract — everything else must route
      through resilience/atomic.py (tmp + fsync + os.replace + sha256
      footer), because a bare binary write crash-truncates in place
      and poisons every later run.  Rule GC008.

  @contract.rank_uniform
      This function's RETURN VALUE is identical on every rank — it is
      derived only from fingerprint-synced config, collective results
      (vote_any / sync_max_ints / process_allgather), or deterministic
      counters that advance in lockstep.  The SPMD-divergence analyzer
      (graftsync, rules GC009/GC010) accepts a branch condition or
      loop bound fed by such a call as rank-uniform; everything else
      defaults to rank-LOCAL, because a collective behind a rank-local
      branch hangs the whole pool with no diagnostic.  Annotating a
      function that actually returns rank-local state disables the
      analyzer's protection for its callers — the annotation is a
      reviewed claim, like parity_oracle's note.  Rules GC009-GC010.

Module marker — jax-free modules declare themselves:

    __jax_free__ = True     # module + its import closure never pull jax

graftlint GL002 discovers its module set from this marker (the
hard-coded list is gone), graftcheck GC002 verifies the whole import
closure, and GC007 requires every module under DECLARE_DIRS to carry
an explicit `__jax_free__ = True/False` so a new serving/io module
cannot silently escape the gate.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, Tuple, TypeVar

__jax_free__ = True

F = TypeVar("F", bound=Callable[..., Any])

#: attribute the runtime decorators tag functions with (introspection
#: convenience only — the analyzer reads the AST, never this attribute)
CONTRACT_ATTR = "__contracts__"

#: module-level marker name declaring a module's jax-freedom
JAX_FREE_MARKER = "__jax_free__"

#: package directories where EVERY module must declare __jax_free__
#: explicitly (True or False) — rule GC007.  A new module dropped into
#: one of these trees is a finding until its author states the import
#: contract one way or the other.
DECLARE_DIRS: Tuple[str, ...] = ("serving", "io", "utils", "analysis",
                                 "native", "parallel", "models",
                                 "resilience", "ingest", "refresh")

#: modules PINNED jax-free: these must declare `__jax_free__ = True` —
#: deleting the marker (or flipping it to False) is a finding (GC007),
#: exactly like removing a parity-oracle annotation.  This is the old
#: hard-coded GL002 list reborn as a registry: discovery governs the
#: GATE (any marked module is checked), the registry governs the SET
#: (the load-bearing fast paths cannot silently leave it).
EXPECTED_JAX_FREE: Tuple[str, ...] = (
    "__init__.py", "__main__.py", "cli.py", "config.py",
    "predict_fast.py",
    "io/__init__.py", "io/parser.py", "io/binning.py", "io/dataset.py",
    "models/__init__.py", "models/tree.py",
    "native/__init__.py",
    "parallel/__init__.py", "parallel/dist.py",
    "serving/__init__.py", "serving/forest.py", "serving/batcher.py",
    "serving/server.py", "serving/fleet.py", "serving/frontend.py",
    # the low-latency lane: the flat-table engine and the host-side
    # rank-encode pack builder it shares with the device matmul route
    # both serve inside backend=native worker processes
    "serving/flatforest.py", "ops/predict_host.py",
    "utils/__init__.py", "utils/log.py", "utils/mt19937.py",
    "utils/compile_cache.py",
    # the fault-tolerance layer rides inside the jax-free fast paths
    # (predict_fast results, serving fallback, CLI snapshot cadence)
    "resilience/__init__.py", "resilience/atomic.py",
    "resilience/backoff.py", "resilience/faults.py",
    "resilience/net.py", "resilience/snapshot.py",
    # out-of-core ingestion: the parse/shard-write paths run in
    # jax-free lanes (CLI task=ingest, multiprocessing parse workers)
    "ingest/__init__.py", "ingest/manifest.py", "ingest/writer.py",
    "ingest/shards.py", "ingest/synth.py",
    # continuous refresh: the deploy agent is a supervisor-family
    # process (watch + subprocess + HTTP) — a jax import here would
    # tax every cycle with a backend init the agent never uses
    "refresh/__init__.py", "refresh/agent.py",
)

# ---------------------------------------------------------------------------
# Fused-body effect signature vocabulary (rule GC005)
# ---------------------------------------------------------------------------

#: canonical inputs EVERY fused step body consumes — the uniform core
#: the composable fused-step builder will be written against
FUSED_CORE: Tuple[str, ...] = ("scores", "valid_scores", "bag", "fmask",
                               "bins", "valid_bins", "gstate", "stopped")

#: body parameter name -> canonical effect-input kind.  A body parameter
#: whose name is missing here is an UNDECLARED input kind (a finding):
#: extend this table deliberately when the builder grows a new input.
CONSUME_KINDS: Mapping[str, str] = {
    "scores": "scores",
    "valid_scores": "valid_scores",
    "bag_mask": "bag", "bag_masks": "bag",
    "fmask": "fmask", "fmasks": "fmask",
    "bins": "bins",
    "valid_bins": "valid_bins",
    "gstate": "gstate",
    "stopped": "stopped",
    "row_order": "order",
    # the last trees' packed rows, which order a re-sort's leaves inside
    "prev_trees": "order",
    # DART device-bank inputs
    "bank_i": "bank", "bank_f": "bank", "leaf_bank": "bank",
    "vbanks": "bank", "t_row": "bank",
    # DART drop/normalize schedule inputs
    "drop_idx": "dart", "drop_count": "dart", "lr": "dart", "kf": "dart",
}

#: collective primitives (matched as jax.lax.X / lax.X in the AST)
COLLECTIVE_OPS: Tuple[str, ...] = (
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "psum_scatter", "axis_index",
)

# ---------------------------------------------------------------------------
# SPMD collective-sequence vocabulary (graftsync, rules GC009-GC011)
# ---------------------------------------------------------------------------

#: host-level collective wrappers exported by parallel/dist.py — the
#: ATOMS of the SPMD sequence model.  Every rank must execute these in
#: an identical order; graftsync verifies the order statically and the
#: runtime tracer (dist.trace_collectives) verifies it live.
HOST_COLLECTIVES: Tuple[str, ...] = (
    "process_allgather", "vote_any", "process_concat", "sync_max_ints",
    "sync_config_by_min", "check_config_fingerprint",
)

#: the ONE module allowed to touch jax.experimental.multihost_utils /
#: jax.distributed directly (rule GC011): every blocking host
#: collective must funnel through its wrappers so it inherits the
#: call_with_deadline degrade-don't-hang wrapping and the runtime
#: trace.  A bare multihost call anywhere else is a finding.
COLLECTIVE_ENTRY_MODULE = "parallel/dist.py"

#: names that are rank-LOCAL no matter what: a branch/loop condition
#: touching one of these can never be rank-uniform.  Matches bare
#: names, parameters, and any attribute segment (`self.rank`,
#: `config.rank` included — a per-rank id stays per-rank wherever it
#: is stored).
RANK_VARYING_NAMES: Tuple[str, ...] = (
    "rank", "process_id", "process_index", "row_rank", "local_rows",
    "local_ips",
)

#: instance-attribute names the analyzer accepts as rank-uniform.
#: Each entry is a reviewed claim about how the attribute is computed;
#: adding one without the justification holding re-opens the silent
#: SPMD-hang class GC009/GC010 exist to close.
RANK_UNIFORM_ATTRS: Tuple[str, ...] = (
    # config-derived (fingerprint-checked by check_config_fingerprint)
    "num_machines", "num_shards", "period", "keep", "max_iteration",
    "resume", "snapshots", "config", "cfg", "params",
    # jax.process_count()-derived flags, identical on every process
    "_mh", "_mh_fused", "_feat_mh",
    # training counters/state that advance in lockstep on every rank
    # (resume agreement pins the starting point, segments advance
    # uniformly, every rank grows the identical model)
    "iter", "num_used_model", "_models", "_bank",
    # bagging-compaction state: the window is config-shaped and the
    # overflow/arranged flags are sync_max_ints-agreed across ranks
    # (gbdt._bag_window_overflow) before anyone acts on them
    "_bag_window", "_bag_overflowed", "_bag_arranged",
    "_fused_sharded",
)

#: external calls whose results are identical on every rank.
#: jax.process_index is deliberately ABSENT — it is the canonical
#: rank-local value.
RANK_UNIFORM_CALLS: Tuple[str, ...] = (
    "jax.process_count", "jax.device_count",
)

# ---------------------------------------------------------------------------
# Lock-order vocabulary (lockgraph, rule GC012)
# ---------------------------------------------------------------------------

#: package functions that BLOCK (device dispatch, model parse+warm,
#: file/socket-bound work): holding a serving hot-path lock across one
#: stalls every thread behind that lock for the operation's duration.
BLOCKING_FUNCTIONS: Tuple[str, ...] = (
    "serving/forest.py::load_forest",
    "serving/forest.py::ServingForest.warm",
    "serving/forest.py::ServingForest.predict",
    "serving/forest.py::ServingForest.predict_text",
    "serving/fleet.py::ModelFleet._load_fresh",
    "serving/batcher.py::MicroBatcher.submit",
)

#: attribute-call terminals treated as blocking operations (socket
#: I/O, subprocess waits, sleeps).  `.wait()` on the HELD condition
#: variable is exempt — releasing the lock while waiting is the whole
#: point of a CV.
BLOCKING_ATTR_CALLS: Tuple[str, ...] = (
    "accept", "recv", "recvfrom", "sendall", "connect", "communicate",
    "sleep", "wait",
)

#: locks ALLOWED to be held across blocking operations, with the
#: justification (rendered in --list-rules style docs).  Everything
#: else is a fast lock: fleet.py's loads-outside-pool-lock discipline,
#: machine-checked instead of comment-enforced.
LOCK_ALLOWED_BLOCKING: Mapping[str, str] = {
    "ModelFleet._load_lock":
        "exists to serialize cold model loads; the pool lock stays "
        "free so warm hits keep serving",
    "ServingState._swap_lock":
        "serializes /reload only and is never taken on the request "
        "path; the old forest keeps serving while the fresh one warms",
}

# ---------------------------------------------------------------------------
# Registries: the annotation SET is part of the contract
# ---------------------------------------------------------------------------

#: the six fused step makers (qualnames are "<module relpath>::<path>"
#: as analysis/callgraph.py renders them).  graftcheck verifies the
#: @contract.fused_body annotation set equals this registry exactly:
#: removing, renaming or adding a maker without updating the registry
#: is a finding (GC005).
EXPECTED_FUSED_BODIES: Tuple[str, ...] = (
    "models/gbdt.py::_make_fused_step",
    "models/gbdt.py::_make_fused_step_reorder",
    "models/gbdt.py::_make_fused_step_dart",
    "models/gbdt.py::_make_fused_step_multi",
    "models/gbdt.py::_make_fused_step_multi_sharded",
    "models/gbdt.py::_make_fused_step_sharded",
)

#: the bit-parity oracle paths (PARITY.md / CONTRACTS.md).  graftcheck
#: verifies the @contract.parity_oracle annotation set equals this
#: registry exactly (GC003).
EXPECTED_PARITY_ORACLES: Tuple[str, ...] = (
    # the general per-tree path: one grow dispatch per tree, the oracle
    # every fused path is structure/value-tested against
    "models/gbdt.py::GBDT._train_tree",
    # K=1 pass-through: iteration batching returns the body UNCHANGED,
    # so K>1 is bit-parity with the per-iteration oracle by construction
    "models/gbdt.py::_batch_iters",
    # the plain fused body: bag_compact=off / masked-bagging oracle
    "models/gbdt.py::_fused_step_body",
    # the growth kernel under full-length masked bagging
    "ops/grow.py::grow_tree",
)


def _tag(fn: F, name: str, args: Dict[str, Any]) -> F:
    """Attach contract metadata; never fail on exotic callables."""
    try:
        contracts = getattr(fn, CONTRACT_ATTR, None)
        if contracts is None:
            contracts = {}
            setattr(fn, CONTRACT_ATTR, contracts)
        contracts[name] = args
    except (AttributeError, TypeError):  # pragma: no cover - jit wrappers
        pass
    return fn


class _Contract:
    """The `contract` namespace — every member is a no-op tagger."""

    @staticmethod
    def traced_pure(fn: F) -> F:
        return _tag(fn, "traced_pure", {})

    @staticmethod
    def parity_oracle(note: str) -> Callable[[F], F]:
        def deco(fn: F) -> F:
            return _tag(fn, "parity_oracle", {"note": note})
        return deco

    @staticmethod
    def jax_free(fn: F) -> F:
        return _tag(fn, "jax_free", {})

    @staticmethod
    def locked_by(lock: str) -> Callable[[F], F]:
        def deco(fn: F) -> F:
            return _tag(fn, "locked_by", {"lock": lock})
        return deco

    @staticmethod
    def fused_body(extras: Tuple[str, ...] = (),
                   collectives: Tuple[str, ...] = ()
                   ) -> Callable[[F], F]:
        def deco(fn: F) -> F:
            return _tag(fn, "fused_body",
                        {"extras": tuple(extras),
                         "collectives": tuple(collectives)})
        return deco

    @staticmethod
    def counted_flush(fn: F) -> F:
        return _tag(fn, "counted_flush", {})

    @staticmethod
    def durable_write(fn: F) -> F:
        return _tag(fn, "durable_write", {})

    @staticmethod
    def rank_uniform(fn: F) -> F:
        return _tag(fn, "rank_uniform", {})


contract = _Contract()

__all__ = ["contract", "CONTRACT_ATTR", "JAX_FREE_MARKER", "DECLARE_DIRS",
           "FUSED_CORE", "CONSUME_KINDS", "COLLECTIVE_OPS",
           "EXPECTED_FUSED_BODIES", "EXPECTED_PARITY_ORACLES",
           "HOST_COLLECTIVES", "COLLECTIVE_ENTRY_MODULE",
           "RANK_VARYING_NAMES", "RANK_UNIFORM_ATTRS",
           "RANK_UNIFORM_CALLS", "BLOCKING_FUNCTIONS",
           "BLOCKING_ATTR_CALLS", "LOCK_ALLOWED_BLOCKING"]
