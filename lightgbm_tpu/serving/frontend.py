"""SO_REUSEPORT multi-process serving front-end.

The single ServingServer is a stdlib HTTP loop behind the GIL: one
process tops out near one core no matter how many handler threads it
spawns.  Production RPS wants processes.  This module runs N worker
processes (`serve_workers`), each the EXISTING ServingServer with its
own warm forest/fleet, all bound to ONE listen port with SO_REUSEPORT —
the kernel load-balances accepted connections across the workers, so no
userspace proxy hop and no shared accept lock.

Workers are plain subprocesses running `python -m
lightgbm_tpu.serving.frontend <cfg.json> <idx> <port>` — a fresh
interpreter per worker (no forked JAX runtime state; each worker warms
its own device forest), independent of how the supervisor itself was
started (CLI, pytest, embedding).

Supervisor duties:
  - pick/reserve the port (serve_port=0 resolves once, workers inherit)
  - spawn workers and detect death + respawn (the `frontend.spawn`
    faultpoint makes spawn failures chaos-testable; a crash loop backs
    off instead of spinning hot)
  - fan SIGTERM/SIGINT out to every worker and wait for each one's
    graceful drain, so no in-flight request is dropped at shutdown

Each worker tags its /healthz and /metrics with its (index, pid) —
repeated scrapes land on different workers (SO_REUSEPORT picks per
connection), so a prober sees the whole fleet's liveness.
"""

from __future__ import annotations

__jax_free__ = True

import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from ..config import Config
from ..resilience.backoff import Backoff
from ..resilience.faults import faultpoint
from ..utils import log

RESPAWN_BACKOFF_S = 0.5
RESPAWN_BACKOFF_MAX_S = 30.0
#: one curve for both crash-loop flavors (pre-ready strikes and
#: post-ready fast deaths) — the shared resilience/backoff helper, so
#: the respawn throttle cannot drift from the connect/deploy retries
_RESPAWN_CURVE = Backoff(base_s=RESPAWN_BACKOFF_S,
                         cap_s=RESPAWN_BACKOFF_MAX_S)
#: consecutive never-became-ready deaths per slot before the supervisor
#: gives up — but ONLY while NO worker has ever signaled readiness (a
#: broken model/config at startup should exit with the diagnostic, like
#: the single-process server does; once the fleet has been healthy,
#: respawns retry forever).  "Ready" is an explicit event handshake —
#: the worker touches its per-slot ready file once its server is
#: listening — NOT a wall-clock age check: under heavy host contention
#: a crash-looping worker can take arbitrarily long to start Python and
#: die, and a time-based classifier misread that as stability (the
#: pre-round-16 flake in test_frontend_startup_crash_loop_gives_up).
STARTUP_CRASH_LIMIT = 3

#: a worker that dies within this long of its spawn DESPITE having
#: completed the readiness handshake throttles its slot's respawns
#: (exponential, same ceiling as the unready path).  Wall clock here
#: paces sleeps ONLY — it never classifies stability or counts toward
#: the give-up, so the contention flake the handshake fixed cannot
#: come back through it (worst case: a healthy respawn waits a bit).
POST_READY_FAST_S = 2.0

#: repo/package parent directory — prepended to the workers' PYTHONPATH
#: so `python -m lightgbm_tpu.serving.frontend` resolves even when the
#: supervisor itself ran from a source checkout without installation
_PKG_PARENT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _worker_main(cfg: Config, idx: int, port: int,
                 ready_path: Optional[str] = None) -> None:
    """Body of one front-end worker process (fresh interpreter, so this
    re-applies the per-process setup the CLI would have done — log
    level, fault schedule).  The device platform is resolved where the
    forest picks its engine (ServingForest._pick_engine ->
    utils/device.py).  Every worker takes the default device: on one
    chip workers 2..N cannot get it, and device_type=tpu makes that
    fatal there instead of a silent CPU-backend worker."""
    log.set_level_from_verbosity(cfg.verbose)
    if cfg.faults:
        from ..resilience.faults import configure
        configure(cfg.faults)
    from .server import ServingServer, run_until_signal
    cfg = dataclasses.replace(cfg, serve_port=port)
    server = ServingServer(cfg, reuse_port=True, worker_index=idx)
    log.info("serve worker %d (pid %d) listening on port %d"
             % (idx, os.getpid(), port))
    if ready_path:
        # readiness handshake: the model parsed, the forest warmed and
        # the socket is listening — only now does the supervisor count
        # this slot as stable (see STARTUP_CRASH_LIMIT).  A marker
        # file, not a pipe: survives supervisor embedding styles and
        # costs one stat per monitor sweep.
        with open(ready_path, "w") as rf:
            rf.write(str(os.getpid()))
    run_until_signal(server)


def worker_entry(argv: List[str]) -> int:
    """`python -m lightgbm_tpu.serving.frontend <cfg.json> <idx>
    <port> [ready_file]` — the subprocess entry the supervisor
    spawns."""
    if len(argv) not in (3, 4):
        log.warning("usage: python -m lightgbm_tpu.serving.frontend "
                    "<cfg.json> <worker_idx> <port> [ready_file]")
        return 2
    with open(argv[0]) as f:
        cfg = Config(**json.load(f))
    _worker_main(cfg, int(argv[1]), int(argv[2]),
                 argv[3] if len(argv) == 4 else None)
    return 0


class Frontend:
    """Supervisor for N SO_REUSEPORT ServingServer worker processes."""

    def __init__(self, cfg: Config):
        if cfg.serve_workers < 2:
            raise ValueError("Frontend wants serve_workers >= 2; use "
                             "ServingServer for a single process")
        if not hasattr(socket, "SO_REUSEPORT"):
            log.fatal("serve_workers > 1 needs SO_REUSEPORT, which "
                      "this platform does not provide")
        self.cfg = cfg
        self.num_workers = int(cfg.serve_workers)
        # supervision runs on the main thread; the lock makes the
        # worker-table/drain-flag stores safe against embedding callers
        # (and keeps the serving lock discipline uniform, GL006)
        self._lock = threading.Lock()
        self._workers: List[Optional[subprocess.Popen]] = \
            [None] * self.num_workers
        self._spawned_at: List[float] = [0.0] * self.num_workers
        self._fast_deaths: List[int] = [0] * self.num_workers
        self._ever_stable = False
        self._draining = False
        self._reserve: Optional[socket.socket] = None
        self._cfg_path: Optional[str] = None
        self._ready_dir: Optional[str] = None
        self.port = cfg.serve_port

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        """Reserve the port, persist the worker config, spawn every
        worker."""
        # bound-but-not-listening + SO_REUSEPORT reserves the port for
        # the workers without joining the kernel's accept distribution
        # (only LISTENING sockets receive connections)
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        s.bind((self.cfg.serve_host, self.cfg.serve_port))
        fd, cfg_path = tempfile.mkstemp(prefix="lgbm_serve_cfg_",
                                        suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump(dataclasses.asdict(self.cfg), f)
        ready_dir = tempfile.mkdtemp(prefix="lgbm_serve_ready_")
        with self._lock:
            self._reserve = s
            self.port = s.getsockname()[1]
            self._cfg_path = cfg_path
            self._ready_dir = ready_dir
        for idx in range(self.num_workers):
            self._spawn(idx)
        log.info("Front-end: %d workers on http://%s:%d (pids %s), "
                 "low-latency lane %s"
                 % (self.num_workers, self.cfg.serve_host, self.port,
                    ",".join(str(p.pid) for p in self._workers
                             if p is not None),
                    self.cfg.serve_low_latency))

    def _ready_path(self, idx: int) -> str:
        assert self._ready_dir is not None
        return os.path.join(self._ready_dir, "worker_%d.ready" % idx)

    def _is_ready(self, idx: int) -> bool:
        """Has this slot's CURRENT worker completed the readiness
        handshake (server listening, marker file written)?"""
        return (self._ready_dir is not None
                and os.path.exists(self._ready_path(idx)))

    def _spawn(self, idx: int) -> None:
        # the spawn seam is chaos-testable: a schedule can fail the
        # Nth (re)spawn to prove the supervisor survives and retries
        faultpoint("frontend.spawn")
        assert self._cfg_path is not None
        # clear the slot's previous handshake: readiness must come from
        # THIS worker, not a dead predecessor's stale marker
        try:
            os.unlink(self._ready_path(idx))
        except OSError:
            pass
        env = dict(os.environ)
        env["PYTHONPATH"] = (_PKG_PARENT + os.pathsep
                             + env.get("PYTHONPATH", ""))
        proc = subprocess.Popen(
            [sys.executable, "-m", "lightgbm_tpu.serving.frontend",
             self._cfg_path, str(idx), str(self.port),
             self._ready_path(idx)],
            env=env)
        with self._lock:
            self._workers[idx] = proc
            self._spawned_at[idx] = time.monotonic()

    def worker_pids(self) -> List[int]:
        return [p.pid for p in self._workers if p is not None]

    # -- supervision -----------------------------------------------------
    def _monitor_once(self, timeout: float = 1.0) -> None:
        """Poll the workers; respawn what died (unless draining).  A
        worker that died WITHOUT completing its readiness handshake is
        crash-looping — back off EXPONENTIALLY so a broken model/config
        does not spin the host at 100% respawning, and if the fleet has
        NEVER been ready (no worker ever wrote its ready marker) give
        up after STARTUP_CRASH_LIMIT strikes per slot: a typo'd
        input_model should exit with the worker's diagnostic, exactly
        like the single-process server does.  Readiness is the event
        handshake from _worker_main, never a wall-clock age — a slow
        host cannot promote a crash-looper to 'stable', nor demote a
        healthy-but-slow startup to a strike."""
        died = False
        for idx, proc in enumerate(list(self._workers)):
            if proc is None or self._draining:
                continue
            ready = self._is_ready(idx)
            code = proc.poll()
            if code is None:
                if ready:
                    with self._lock:
                        self._ever_stable = True
                        # the post-ready throttle counter clears only
                        # once the worker has SURVIVED the fast window
                        # — an alive sweep landing between a 0.2 s
                        # handshake and a 1.5 s crash must not reset
                        # the escalation (pacing only, like the rest
                        # of the wall-clock use here)
                        if (time.monotonic() - self._spawned_at[idx]
                                >= POST_READY_FAST_S):
                            self._fast_deaths[idx] = 0
                continue
            died = True
            # re-sample AFTER poll observed the death: a worker that
            # wrote its marker and exited between the two calls above
            # must not be misread as a pre-ready strike (the marker
            # state is final once the process is dead)
            ready = ready or self._is_ready(idx)
            fast = not ready   # died before ever serving = a strike
            throttle = 0
            if ready:
                # the worker completed its handshake before dying — the
                # fleet WAS healthy (credit it even when the death fell
                # between two sweeps), so this death never counts toward
                # the startup give-up.  It still THROTTLES: a worker
                # that keeps crashing moments after becoming ready
                # would otherwise respawn at full interpreter-spawn
                # speed forever — back its slot off exponentially
                # (pacing only; see POST_READY_FAST_S).
                fast_post = (time.monotonic() - self._spawned_at[idx]
                             < POST_READY_FAST_S)
                with self._lock:
                    self._ever_stable = True
                    if fast_post:
                        self._fast_deaths[idx] += 1
                        throttle = self._fast_deaths[idx]
                    else:
                        self._fast_deaths[idx] = 0
            log.warning("serve worker %d (pid %s) died (exit %s)%s — "
                        "respawning"
                        % (idx, proc.pid, code,
                           " before its readiness handshake (crash-"
                           "loop backoff)" if fast else ""))
            if fast:
                with self._lock:
                    self._fast_deaths[idx] += 1
                    throttle = self._fast_deaths[idx]
                    hopeless = not self._ever_stable and all(
                        n >= STARTUP_CRASH_LIMIT
                        for n in self._fast_deaths)
                if hopeless:
                    log.fatal(
                        "every serve worker crash-looped %d times at "
                        "startup (see the worker diagnostics above) — "
                        "giving up instead of respawning forever"
                        % STARTUP_CRASH_LIMIT)
            if throttle:
                # one backoff curve for both crash-loop flavors
                # (pre-ready strikes and post-ready fast deaths)
                time.sleep(_RESPAWN_CURVE.delay(throttle))
            try:
                self._spawn(idx)
            except Exception as ex:
                # an injected (or real) spawn failure: keep the rest of
                # the fleet serving, retry this slot on the next sweep
                with self._lock:
                    self._workers[idx] = None
                log.warning("serve worker %d respawn failed (%s: %s); "
                            "retrying" % (idx, type(ex).__name__, ex))
        if not died:
            time.sleep(timeout)

    def _sweep_empty_slots(self) -> None:
        if self._draining:
            return
        for idx, proc in enumerate(self._workers):
            if proc is None:
                try:
                    self._spawn(idx)
                except Exception as ex:
                    log.warning("serve worker %d respawn failed "
                                "(%s: %s); retrying"
                                % (idx, type(ex).__name__, ex))

    def shutdown(self, drain_timeout: float = 30.0) -> None:
        """SIGTERM fan-out + graceful join: every worker drains its
        in-flight requests (ServingServer.shutdown inside the worker);
        stragglers past the timeout are killed."""
        with self._lock:
            self._draining = True
        for proc in self._workers:
            if proc is not None and proc.poll() is None:
                try:
                    proc.terminate()   # SIGTERM: worker drains
                except OSError:
                    pass
        deadline = time.monotonic() + drain_timeout
        for proc in self._workers:
            if proc is None:
                continue
            try:
                proc.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                log.warning("serve worker pid %s did not drain in %gs; "
                            "killing" % (proc.pid, drain_timeout))
                proc.kill()
                try:
                    proc.wait(5.0)
                except subprocess.TimeoutExpired:
                    pass
        if self._reserve is not None:
            self._reserve.close()
            with self._lock:
                self._reserve = None
        if self._cfg_path is not None:
            try:
                os.unlink(self._cfg_path)
            except OSError:
                pass
            with self._lock:
                self._cfg_path = None
        if self._ready_dir is not None:
            import shutil
            shutil.rmtree(self._ready_dir, ignore_errors=True)
            with self._lock:
                self._ready_dir = None

    def run_forever(self) -> None:
        """Supervise until SIGTERM/SIGINT, then fan out the drain."""
        stop = threading.Event()

        def _on_signal(signum: int, frame: Any) -> None:
            log.info("Signal %d: draining %d workers..."
                     % (signum, self.num_workers))
            stop.set()

        prev: Dict[int, Any] = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev[sig] = signal.signal(sig, _on_signal)
        try:
            while not stop.is_set():
                self._monitor_once(timeout=0.5)
                self._sweep_empty_slots()
        finally:
            for sig, h in prev.items():
                signal.signal(sig, h)
            self.shutdown()
            log.info("Front-end drained, exiting")


def frontend_forever(cfg: Config) -> None:
    """CLI entry (task=serve with serve_workers > 1)."""
    fe = Frontend(cfg)
    fe.start()
    fe.run_forever()


if __name__ == "__main__":   # pragma: no cover - subprocess entry
    sys.exit(worker_entry(sys.argv[1:]))
