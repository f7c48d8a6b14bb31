"""Test configuration: force an 8-device virtual CPU platform and x64.

NOTE: pytest's plugin discovery (flax/chex entry points) imports jax before
this conftest executes, so setting JAX_PLATFORMS in os.environ here is too
late — but the backend initializes lazily, so jax.config.update still wins
as long as no test touched a device yet.  XLA_FLAGS is read by the CPU
client at backend creation, which is also still ahead of us.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
# The tier-1 process runs without the persistent compilation cache.
# Re-tested under jaxlib 0.9.0 (PR 21): the heap corruption 0.4.36
# showed on this CPU backend is gone — two passes over ~50 tests with
# the cache on, cold then warm, complete with identical results — but
# every cached load logs an XLA:CPU AOT "machine feature
# +prefer-no-scatter is not supported ... could lead to SIGILL" error,
# and a cache shared across runs makes compile counts (xla_guard)
# depend on what ran before.  Tests stay hermetic; production keeps the
# cache.  setdefault: an operator who explicitly configured it wins.
os.environ.setdefault("LGBM_TPU_NO_COMPILE_CACHE", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# compile/transfer-budget fixture (lightgbm_tpu/analysis/guards.py):
# `with xla_guard(0, what="..."):` pins recompile invariants in tests
from lightgbm_tpu.analysis.guards import (xla_guard,  # noqa: E402,F401
                                          collective_trace)  # noqa: F401

REFERENCE_DIR = "/root/reference"
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

# -- thread-leak gate --------------------------------------------------------
# The serving/batcher/prefetch/frontend subsystems all spawn worker
# threads; a test that forgets to drain one leaks it into every later
# test (and, before this gate, nothing noticed).  Modules opt in with
# `pytestmark = pytest.mark.usefixtures("no_leaked_threads")`.
#
# Two classes are gated: (1) NO new non-daemon thread may survive (a
# non-daemon leak hangs interpreter exit), and (2) no new thread with a
# known worker-pool name may survive even if daemonic — the prefetch
# stager ("lgbm-window-prefetch") and the micro-batcher loop
# ("serve-batcher") are daemon threads precisely so a crash can't hang
# exit, which also meant nothing ever asserted they shut down.

import threading  # noqa: E402
import time as _time  # noqa: E402

import pytest  # noqa: E402

_GATED_THREAD_NAMES = ("lgbm-window-prefetch", "serve-batcher",
                       "lgbm-refresh-")


@pytest.fixture
def no_leaked_threads():
    before = {t.ident for t in threading.enumerate()}
    yield

    def leaked():
        out = []
        for t in threading.enumerate():
            if t.ident in before or not t.is_alive():
                continue
            if not t.daemon or any(t.name.startswith(n)
                                   for n in _GATED_THREAD_NAMES):
                out.append(t)
        return out

    # drains are asynchronous (shutdown joins, event handshakes): give
    # stragglers a bounded grace window before calling it a leak
    deadline = _time.monotonic() + 5.0
    while leaked() and _time.monotonic() < deadline:
        _time.sleep(0.05)
    rest = leaked()
    assert not rest, (
        "test leaked thread(s): %s — every server/batcher/prefetch/"
        "frontend the test started must be shut down (daemon worker "
        "threads included for the gated pools)"
        % ", ".join("%s(daemon=%s)" % (t.name, t.daemon) for t in rest))
