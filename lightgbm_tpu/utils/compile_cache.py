"""Persistent XLA compilation cache.

The fused training step costs tens of seconds of XLA compilation per
(shape, config) the first time it runs.  JAX's persistent compilation
cache makes that a one-time cost per cache directory: later processes
deserialize the compiled executable instead.

Where the directory is: `JAX_COMPILATION_CACHE_DIR`, when the
environment sets it — JAX reads it itself and this module sets no
directory in code; likewise one an embedding process already set
through `jax.config`.  Otherwise `<checkout>/.jax_cache` (gitignored): a
fixed path with no pid, time or tempdir component, inside the tree a
chip run copies.  In both cases the min-compile-time and
min-entry-size thresholds are dropped so sub-second jits are cached too.

Enabled by the modules that trace jits (ops/histogram, ops/split,
ops/predict, ops/hist_pallas, objectives) before their first compile —
NOT on package import, which stays jax-free so the native task=predict
fast path (predict_fast.py) skips the JAX startup cost entirely.  Opt
out with LGBM_TPU_NO_COMPILE_CACHE=1.

THE COMPILE LEDGER.  The same call registers one pair of JAX monitoring
listeners (cache or no cache) that keeps, for every executable this
process traced, lowered, compiled or loaded, one record: its name as JAX
gives it (`jit(step)`), when (process age), the four durations, whether
the persistent cache had it, and the context it happened in: the open
start-up span (utils/spans.py), or the open `lgbm.enqueue`'s (kind, k,
shards), else `other`.  `ledger()` returns the records, `totals()` their
sums, `startup_line()` the one line a training job logs when its first
trees reach the host.
"""

__jax_free__ = True

import os
import threading
from typing import Any, Dict, List, Optional

from . import log, spans

#: <checkout>/.jax_cache — this file is lightgbm_tpu/utils/compile_cache.py
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled = False

# -- the compile ledger ----------------------------------------------------
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: a record's keys.  The four durations are disjoint: `trace_s` the
#: Python tracing of the function (what it traced inside counts once),
#: `lower_s` jaxpr to MLIR, `retrieval_s` the persistent cache's load,
#: `backend_s` the rest of the backend event: the compile proper on a
#: miss, next to nothing on a hit.  `hit`: the cache had it (False: it
#: compiled here; None: nothing reached the backend, a trace or a
#: lowering alone).  `context`: a start-up span's name, spans.ENQUEUE
#: (then `call` is [kind, k, shards]) or "other".
LEDGER_FIELDS = ("fun", "t0", "trace_s", "lower_s", "backend_s",
                 "retrieval_s", "hit", "context", "call")
LEDGER_CAP = 4096       # records kept; totals() counts every one
OTHER = "other"

_ledger: List[Dict[str, Any]] = []
_totals: Dict[str, Any] = {
    "executables": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
    "retrieval_s": 0.0, "hits": 0, "misses": 0, "dropped": 0}


class _Assembly(threading.local):
    """One thread's events on their way to a record (a compile runs on
    the thread that called the function)."""

    def __init__(self) -> None:
        self.traces: list = []      # (start, seconds, name), none inside
        #                             another, not yet lowered
        self.open: Optional[Dict[str, Any]] = None  # lowered, not compiled
        self.hit: Optional[bool] = None
        self.retrieval_s = 0.0


_assembly = _Assembly()
_listening = False


def _record(fun: str, start: float, trace_s: float = 0.0,
            lower_s: float = 0.0) -> Dict[str, Any]:
    return {"fun": fun, "t0": spans.process_age(start), "trace_s": trace_s,
            "lower_s": lower_s, "backend_s": 0.0, "retrieval_s": 0.0,
            "hit": None, "context": OTHER, "call": None}


def _close(record: Dict[str, Any]) -> None:
    """Name the record's context and keep it.  A context that is no
    start-up span's name is an open lgbm.enqueue (models/gbdt.py
    `_enqueue`), which takes the record and says whose call it is."""
    contexts = spans.open_contexts()
    if contexts:
        top = contexts[-1]
        if isinstance(top, str):
            record["context"] = top
        else:
            record["context"] = spans.ENQUEUE
            record["call"] = top.compiled_inside(record)
    for field in ("trace_s", "lower_s", "backend_s", "retrieval_s"):
        _totals[field] += record[field]
    if record["hit"] is not None:
        _totals["executables"] += 1
        _totals["hits" if record["hit"] else "misses"] += 1
    if len(_ledger) < LEDGER_CAP:
        _ledger.append(record)
    else:
        _totals["dropped"] += 1


def _drop_inner(traces: list, start: float) -> None:
    """Forget the traces that began at or after `start`: they ran inside
    the trace or lowering that began there, whose seconds hold them."""
    while traces and traces[-1][0] >= start:
        traces.pop()


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    asm = _assembly
    if event == TRACE_EVENT:
        start = spans.clock() - duration
        _drop_inner(asm.traces, start)
        asm.traces.append((start, duration, kw.get("fun_name", "")))
    elif event == LOWER_EVENT:
        start = spans.clock() - duration
        fun = kw.get("fun_name", "")
        _drop_inner(asm.traces, start)
        # its own trace is the newest of its name: jit(step) after step
        own = next((t for t in reversed(asm.traces)
                    if fun.endswith("(%s)" % t[2])), None)
        pending = [asm.open] if asm.open is not None else []
        pending += [_record(t[2], t[0], trace_s=t[1])
                    for t in asm.traces if t is not own]
        for record in pending:      # lowered or traced, and no more
            _close(record)
        asm.traces.clear()
        asm.open = (_record(fun, start, lower_s=duration) if own is None else
                    _record(fun, own[0], trace_s=own[1], lower_s=duration))
    elif event == RETRIEVAL_EVENT:
        asm.retrieval_s += duration
    elif event == BACKEND_EVENT:
        fun = kw.get("fun_name", "")
        record = asm.open
        if record is None or record["fun"] != fun:
            if record is not None:
                _close(record)
            record = _record(fun, spans.clock() - duration)
        record["retrieval_s"] = asm.retrieval_s
        record["backend_s"] = max(duration - asm.retrieval_s, 0.0)
        record["hit"] = bool(asm.hit)
        asm.open, asm.hit, asm.retrieval_s = None, None, 0.0
        _close(record)


def _on_event(event: str, **_kw: Any) -> None:
    # heard between the cache's request and the backend event
    if event == HIT_EVENT:
        _assembly.hit = True
    elif event == MISS_EVENT:
        _assembly.hit = False


def _listen() -> None:
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def ledger() -> List[Dict[str, Any]]:
    """The records so far, oldest first (LEDGER_FIELDS)."""
    return list(_ledger)


def totals() -> Dict[str, Any]:
    """Sums over every record since the listeners were registered:
    `executables` (records that reached the backend), the four
    durations, `hits`, `misses`, `dropped` (records past LEDGER_CAP:
    summed here, not kept)."""
    return dict(_totals)


def _s(seconds: Optional[float]) -> str:
    return "?" if seconds is None else "%.1f" % seconds


def startup_line() -> str:
    """What a job's start cost, in one line, from the start-up records
    and the ledger's totals (README.md, "Profiling a training job")."""
    spent: Dict[str, float] = {}
    upload_bytes = 0
    bank = ""
    for r in spans.startup_records():
        spent[r["name"]] = spent.get(r["name"], 0.0) + r["dur"]
        if r["name"] == spans.STARTUP_UPLOAD:
            upload_bytes += r["stats"].get("bytes", 0)
        if r["stats"].get("bank_cap"):      # a DART job: its bank's bound
            bank = ("; DART leaf bank %d trees, %.2f GB"
                    % (r["stats"]["bank_cap"],
                       r["stats"]["bank_bytes"] / 1e9))
    at = spans.stamps()
    t = totals()
    return ("start-up: first dispatch at %s s (objective %s, booster %s of "
            "it upload %s of %.2f GB); %d executables, first calls %s s "
            "(trace %s, lower %s, backend %s), %d hits %d misses; first "
            "tree on the host at %s s"
            % (_s(at.get(spans.FIRST_DISPATCH)),
               _s(spent.get(spans.STARTUP_OBJECTIVE, 0.0)),
               _s(spent.get(spans.STARTUP_BOOSTER, 0.0)),
               _s(spent.get(spans.STARTUP_UPLOAD, 0.0)), upload_bytes / 1e9,
               t["executables"], _s(spent.get(spans.FIRST_CALL, 0.0)),
               _s(t["trace_s"]), _s(t["lower_s"]),
               _s(t["backend_s"] + t["retrieval_s"]), t["hits"],
               t["misses"], _s(at.get(spans.FIRST_TREE))) + bank)


def enable_compilation_cache() -> None:
    """Idempotently turn on JAX's persistent compilation cache (see the
    module docstring for where it lives) with every executable
    eligible, and the compile ledger's listeners first: a job without
    the cache compiles all the more.  A cache that cannot be set up
    costs compile time, not correctness: the failure is logged and
    training goes on."""
    global _enabled
    _listen()
    if _enabled or os.environ.get("LGBM_TPU_NO_COMPILE_CACHE") == "1":
        return
    _enabled = True
    import jax
    try:
        # the environment's directory, or one an embedding process
        # already set through jax.config, is left alone
        if not (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or jax.config.jax_compilation_cache_dir):
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except (OSError, AttributeError) as ex:
        log.warning("Persistent compilation cache not enabled: %s" % ex)
