"""Multi-host bootstrap: machine_list_file -> jax.distributed.

The reference brings up its own TCP mesh from a machine list file (ip port
per line, optional "rank=i" override; rank inferred by matching local IPs
— src/network/linkers_socket.cpp:20-108) and then runs hand-written
collectives over it.  Here the same user-facing surface bootstraps the JAX
distributed runtime instead: the FIRST machine in the list acts as the
coordinator, every process calls jax.distributed.initialize, and all
cross-host traffic rides XLA collectives over ICI/DCN — the entire
src/network/ layer (Bruck allgather, recursive-halving reduce-scatter,
socket/MPI linkers, ~1,150 LoC) has no equivalent here by design.

Host-side (numpy) exchanges — bin mappers at load time — go through
process_allgather (jax.experimental.multihost_utils).

This module is the ONE sanctioned multihost entry point (graftsync
GC011): every wrapper funnels through process_allgather, so every
host collective inherits the per-collective deadline AND the runtime
collective trace.  trace_collectives() captures a per-rank ring
buffer of (name, shape, dtype, callsite) events — off by default,
enabled by the 2-process trace test (tests/test_graftsync.py) which
asserts rank traces are identical and every callsite is one the
static analyzer predicted (graftsync.collective_sites).
"""

from __future__ import annotations

__jax_free__ = True

import socket
import sys
from collections import deque
from contextlib import contextmanager
from typing import (Deque, Iterator, List, NamedTuple, Optional,
                    Tuple)

import numpy as np

from ..resilience.faults import faultpoint
# NetworkError re-exported: transport callers catch it from here
from ..resilience.net import NetworkError as NetworkError
from ..resilience.net import call_with_deadline, connect_with_retry
from ..utils import log

#: per-collective deadline in seconds (0 = wait forever); configured by
#: init_distributed from config.dist_timeout_s.  A dead peer then
#: raises NetworkError out of the blocked collective instead of
#: hanging the trainer indefinitely.
_COLLECTIVE_TIMEOUT = [0.0]


def set_network_timeout(seconds: float) -> None:
    _COLLECTIVE_TIMEOUT[0] = max(0.0, float(seconds))


# ---------------------------------------------------------------------------
# Runtime collective tracer (off by default; ~one list lookup when off)
# ---------------------------------------------------------------------------

class CollectiveEvent(NamedTuple):
    """One host collective as this rank executed it."""
    name: str              # dist.py wrapper the caller used (vote_any, ...)
    shape: Tuple[int, ...]
    dtype: str
    callsite: str          # "file.py:line" of the first frame outside dist


#: the active ring buffer, or None when tracing is off
_TRACE: List[Optional[Deque[CollectiveEvent]]] = [None]


def _record_collective(array: np.ndarray) -> None:
    """Append one event to the active trace.  Every wrapper funnels
    through process_allgather, so recording there sees them all; the
    logical name is the OUTERMOST dist.py frame (the wrapper the
    caller invoked — process_concat's two allgathers both trace as
    process_concat), the callsite the first frame outside it."""
    buf = _TRACE[0]
    if buf is None:
        return
    arr = np.asarray(array)
    frame = sys._getframe(1)
    name = "process_allgather"
    while frame is not None and frame.f_code.co_filename == __file__:
        # skip lambdas (make_metric_reducer's sum-reduce closure lives
        # in this file): the logical name is the outermost NAMED
        # wrapper, so a metric-eval allgather traces as
        # process_allgather, not "<lambda>"
        if not frame.f_code.co_name.startswith("<"):
            name = frame.f_code.co_name
        frame = frame.f_back
    callsite = "<unknown>"
    if frame is not None:
        callsite = "%s:%d" % (frame.f_code.co_filename, frame.f_lineno)
    buf.append(CollectiveEvent(name, tuple(arr.shape), str(arr.dtype),
                               callsite))


@contextmanager
def trace_collectives(capacity: int = 1024
                      ) -> Iterator["Deque[CollectiveEvent]"]:
    """Enable the per-rank collective ring buffer for a with-block and
    yield it (a deque capped at `capacity`: steady-state training can
    run under the tracer without unbounded growth).  Exposed to tests
    as the `collective_trace` fixture (analysis/guards.py), the same
    pattern as xla_guard."""
    prev = _TRACE[0]
    buf: Deque[CollectiveEvent] = deque(maxlen=max(1, int(capacity)))
    _TRACE[0] = buf
    try:
        yield buf
    finally:
        _TRACE[0] = prev


def parse_machine_list(path: str) -> List[Tuple[str, int]]:
    """machine_list_file: one "ip port" per line; '#' comments; blank lines
    skipped (reference linkers_socket.cpp:24-45)."""
    machines: List[Tuple[str, int]] = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.replace(":", " ").split()
            if len(parts) < 2:
                log.fatal("Invalid machine list line: %r" % line)
            machines.append((parts[0], int(parts[1])))
    return machines


def local_ip_list() -> List[str]:
    """Best-effort list of this host's IPs (TcpSocket::GetLocalIpList,
    reference socket_wrapper.hpp)."""
    ips = {"127.0.0.1", "localhost"}
    try:
        hostname = socket.gethostname()
        ips.add(hostname)
        for info in socket.getaddrinfo(hostname, None):
            ips.add(info[4][0])
    except OSError:
        pass
    return sorted(ips)


def infer_rank(machines: List[Tuple[str, int]], listen_port: int,
               local_ips: Optional[List[str]] = None) -> int:
    """This process's rank = the machine-list entry matching one of our
    local IPs AND the local_listen_port (several ranks may share an IP
    when run on one host with distinct ports — reference
    linkers_socket.cpp:49-77)."""
    ips = set(local_ips if local_ips is not None else local_ip_list())
    matches = [i for i, (ip, port) in enumerate(machines)
               if ip in ips and port == listen_port]
    if len(matches) == 1:
        return matches[0]
    # fall back to ip-only match when the port is not distinguishing
    ip_matches = [i for i, (ip, _) in enumerate(machines) if ip in ips]
    if len(ip_matches) == 1:
        return ip_matches[0]
    log.fatal("Cannot infer machine rank from %r (local ips %r, port %d)"
              % (machines, sorted(ips), listen_port))


def init_distributed(config) -> Tuple[int, int]:
    """Bring up the JAX distributed runtime per the reference's
    machine-list surface; returns (rank, num_machines).  No-op (0, 1)
    when num_machines <= 1."""
    if config.num_machines <= 1:
        return 0, 1
    machines = parse_machine_list(config.machine_list_file)
    if len(machines) < config.num_machines:
        log.fatal("machine_list_file has %d entries < num_machines=%d"
                  % (len(machines), config.num_machines))
    machines = machines[:config.num_machines]
    rank = infer_rank(machines, config.local_listen_port)
    coordinator = "%s:%d" % machines[0]
    import jax

    plat = jax.config.jax_platforms
    if plat is None or "cpu" in plat:
        # CPU-only clusters (CI, local smoke runs): cross-process
        # collectives need the gloo implementation — without it the
        # compiler rejects multiprocess computations outright.  None =
        # automatic backend selection, which may well land on CPU; the
        # setting only configures the CPU client, so it is harmless
        # when an accelerator wins.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # connect with exponential backoff under an overall deadline (the
    # reference's linkers_socket.cpp:24-45 retry loop, typed): the
    # coordinator routinely comes up AFTER the workers in a preemptible
    # pool, and a refused first connect must not kill the job
    def _connect() -> None:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=config.num_machines,
                                   process_id=rank)

    connect_with_retry(
        _connect, "jax.distributed.initialize(%s)" % coordinator,
        deadline_s=float(config.dist_connect_deadline_s))
    set_network_timeout(float(config.dist_timeout_s))
    log.info("Distributed runtime up: rank %d/%d (coordinator %s)"
             % (rank, config.num_machines, coordinator))
    return rank, config.num_machines


def process_allgather(array: np.ndarray) -> np.ndarray:
    """Allgather a host array across processes -> stacked [num_processes,
    ...] (replaces Network::Allgather for load-time metadata).

    Runs under the configured collective deadline: a dead peer raises a
    typed NetworkError instead of blocking forever (degrade-don't-hang;
    resilience/net.py).  The dist.send/dist.recv faultpoints bracket
    the exchange for deterministic chaos schedules."""
    from jax.experimental import multihost_utils

    faultpoint("dist.send")
    _record_collective(array)
    out = call_with_deadline(
        lambda: np.asarray(multihost_utils.process_allgather(array)),
        _COLLECTIVE_TIMEOUT[0], "process_allgather")
    faultpoint("dist.recv")
    if out.ndim == np.ndim(array):
        # a 1-process runtime returns the input unchanged; normalize to
        # the documented stacked [num_processes, ...] shape so callers
        # (and single-process tests of the mh agreement paths) see one
        # contract at any process count
        out = out[None]
    return out


def vote_any(flag: bool) -> bool:
    """Cross-rank boolean OR (one int64 allgather): True when ANY rank
    votes True.  The one primitive behind early-stop agreement and
    preemption agreement — both must see the identical collective."""
    votes = process_allgather(np.array([int(flag)], dtype=np.int64))
    return bool(votes.sum() > 0)


def process_concat(array: np.ndarray) -> np.ndarray:
    """Concatenate per-process host arrays of DIFFERENT leading lengths
    along axis 0 (rank order).  process_allgather needs equal shapes, so
    lengths are gathered first and data is padded to the max."""
    array = np.ascontiguousarray(array)
    lens = process_allgather(np.array([array.shape[0]], dtype=np.int64))
    lens = lens.reshape(-1)
    mx = int(lens.max())
    pad = np.zeros((mx,) + array.shape[1:], dtype=array.dtype)
    pad[:array.shape[0]] = array
    stacked = process_allgather(pad)          # [P, mx, ...]
    return np.concatenate([stacked[p, :int(lens[p])]
                           for p in range(stacked.shape[0])], axis=0)


def sync_max_ints(values) -> np.ndarray:
    """Element-wise max of a small int vector across processes — shard
    metadata agreement (the query-sharded rank layout needs every process
    to build identically-shaped gradient-state blocks: per-shard row
    capacity, longest query, max queries per shard)."""
    vals = np.asarray(values, dtype=np.int64).reshape(-1)
    return process_allgather(vals).max(axis=0)


def sync_config_by_min(config) -> None:
    """The reference's GlobalSyncUpByMin (application.cpp:119,188-193 +
    255-282): allreduce-min the RNG seeds and feature_fraction so ranks
    with inconsistent configs cannot silently grow different trees.
    Mutates config in place on every rank to the global minimum."""
    vals = np.array([config.feature_fraction_seed,
                     config.data_random_seed,
                     config.bagging_seed,
                     config.drop_seed], dtype=np.int64)
    frac = np.array([config.feature_fraction], dtype=np.float64)
    gi = process_allgather(vals).min(axis=0)
    gf = process_allgather(frac).min(axis=0)
    config.feature_fraction_seed = int(gi[0])
    config.data_random_seed = int(gi[1])
    config.bagging_seed = int(gi[2])
    config.drop_seed = int(gi[3])
    config.feature_fraction = float(gf[0])


def check_config_fingerprint(config) -> None:
    """Fatal when ranks disagree on any tree-shaping hyper-parameter —
    the silent-divergence class GlobalSyncUpByMin cannot repair.  The
    fingerprint covers everything that shapes the SPMD computation;
    paths/ports that legitimately differ per rank are excluded."""
    import hashlib
    keys = ("objective", "boosting_type", "tree_learner", "num_class",
            "num_iterations", "num_leaves", "max_depth", "max_bin",
            "min_data_in_leaf", "min_sum_hessian_in_leaf", "learning_rate",
            "lambda_l1", "lambda_l2", "min_gain_to_split",
            "feature_fraction", "feature_fraction_seed", "bagging_fraction",
            "bagging_freq", "bagging_seed", "early_stopping_round",
            "metric", "metric_freq", "hist_dtype", "hist_impl", "hist_agg",
            "num_shards", "top_k", "drop_rate", "drop_seed", "sigmoid",
            "num_machines", "is_training_metric")
    desc = ";".join("%s=%r" % (k, getattr(config, k, None)) for k in keys)
    # the number of valid sets shapes the per-eval collective schedule
    # (each metric eval allreduces): ranks must agree on it too
    desc += ";num_valid=%d" % len(getattr(config, "valid_data", []) or [])
    h = np.frombuffer(hashlib.sha256(desc.encode()).digest()[:8],
                      dtype=np.int64)
    all_h = process_allgather(h).reshape(-1)
    if not (all_h == all_h[0]).all():
        log.fatal("Inconsistent training configs across machines "
                  "(config fingerprints differ); every rank must use "
                  "identical hyper-parameters: %s" % desc)


def make_metric_reducer():
    """(sum_reduce, concat) callables for Metric.set_reducer: partial
    metric sums allreduce across ranks; order-sensitive metrics (AUC)
    concatenate raw columns instead."""
    return (lambda parts: process_allgather(
                np.asarray(parts, dtype=np.float64)).sum(axis=0),
            process_concat)
