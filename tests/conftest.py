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

REFERENCE_DIR = os.environ.get("LGT_REFERENCE_DIR", "/root/reference")
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


# -- example files -----------------------------------------------------------
# Tests that need *a* file of the reference's formats and shapes, not the
# reference's own bytes, read the `examples` fixture: the mounted
# <reference>/examples where there is one, else files written once a
# session from a fixed seed, in the same layout.  Tests that compare
# with the reference's own output (tests/golden/) read REFERENCE_DIR and
# skip without it.

import numpy as np  # noqa: E402

EXAMPLES_SEED = 20161017


def write_tsv(path, y, x):
    """Label first, tab-separated, three decimals: the example files'."""
    with open(path, "w") as f:
        for label, row in zip(y, x):
            f.write("\t".join(["%d" % label] + ["%.3f" % v for v in row])
                    + "\n")


def write_examples(root, seed=EXAMPLES_SEED):
    """binary_classification/binary.{train,test} (7,000 and 500 x 28,
    label first, TSV), regression/regression.test (500 x 28, TSV) and
    lambdarank/rank.test (+ .query; LibSVM, zeros left out) under root."""
    rng = np.random.RandomState(seed)

    def rows(n):
        x = rng.randn(n, 28)
        x[:, 20:] = np.abs(x[:, 20:]) * (rng.rand(n, 8) < 0.6)   # sparse-ish
        score = (1.4 * x[:, 0] - 1.1 * x[:, 1] + x[:, 2] * x[:, 3]
                 + 0.8 * (np.abs(x[:, 4]) - 0.8) + 0.6 * x[:, 20])
        return x, score

    for sub in ("binary_classification", "regression", "lambdarank"):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
    for name, n in (("binary.train", 7000), ("binary.test", 500)):
        x, score = rows(n)
        write_tsv(os.path.join(root, "binary_classification", name),
                  score + rng.logistic(size=n) > 0, x)
    x, score = rows(500)
    write_tsv(os.path.join(root, "regression", "regression.test"),
              score + rng.logistic(size=500) > 0, x)
    sizes = rng.randint(5, 30, size=50)
    x, score = rows(int(sizes.sum()))
    rel = np.clip(np.round(score + rng.randn(len(score)) + 1.0), 0, 4)
    path = os.path.join(root, "lambdarank", "rank.test")
    with open(path, "w") as f:
        for label, row in zip(rel, x):
            f.write(" ".join(["%d" % label] + [
                "%d:%.3f" % (j + 1, v) for j, v in enumerate(row)
                if "%.3f" % v not in ("0.000", "-0.000")]) + "\n")
    with open(path + ".query", "w") as f:
        f.write("".join("%d\n" % q for q in sizes))


@pytest.fixture(scope="session")
def examples(tmp_path_factory):
    """The directory of example files (see above)."""
    mounted = os.path.join(REFERENCE_DIR, "examples")
    if os.path.isdir(mounted):
        return mounted
    root = str(tmp_path_factory.mktemp("examples"))
    write_examples(root)
    return root
