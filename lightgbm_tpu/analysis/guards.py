"""Runtime guards: XLA compile counting and host<->device transfer
accounting.

The serving forest promises "steady state never recompiles" (its
power-of-two row buckets pre-compile in warm()) and the fused training
step promises one compile per (shape, config); until now nothing
measured either.  `track_compiles()` captures jax's own compile logging
("Compiling <name> ..." lowering records and "Finished XLA compilation"
backend records) through a logging.Handler while jax_log_compiles is
force-enabled, so a test can assert an exact compile budget.  Cache
HITS (the jit C++ fast path) log nothing — a steady-state dispatch of
an already-compiled executable counts zero.

Counted signals:
  * stats.compiles — lowerings ("Compiling ..."): every trace+lower of
    a new (shape, config) key, whether or not the backend compile is
    later served from the persistent cache.  This is the recompile
    signal the invariants are stated in.
  * stats.backend_compiles — XLA backend compile records.  CAVEAT: the
    dispatch timing record fires for persistent-cache DESERIALIZATION
    too, so this over-counts on cache-warm processes — use the
    cache_hits/cache_misses pair to split them.
  * stats.cache_hits / cache_misses — persistent compilation cache
    probes (jax lru_cache "Cache hit for key" records and the
    compiler's "PERSISTENT COMPILATION CACHE MISS" records).  A fresh
    process of an already-seen shape shows misses == 0: the cross-run
    zero-compile claim (tests/test_cache_cross_process.py; the
    benchmark's setup_compile_s).
  * stats.device_puts / device_gets — explicit jax.device_put /
    jax.device_get calls made through the `jax` module attributes
    (wrapped for the duration).  Implicit transfers are policed by the
    `transfer_guard` argument, which forwards to jax.transfer_guard
    (e.g. "disallow" makes any implicit transfer raise).

Use either the raw tracker or the budget-asserting wrapper:

    with track_compiles() as stats:
        f(x)
    assert stats.compiles == 1

    with compile_budget(max_compiles=0, what="serving steady state"):
        forest.predict(rows, "raw")

Pytest: the `xla_guard` fixture (registered via tests/conftest.py)
returns `compile_budget`, so tests write
`with xla_guard(0, what="..."):`.

Thread-safe enough for the serving tests: the capture handler appends
from whatever thread compiles (batcher workers included); list.append
is atomic under the GIL.
"""

from __future__ import annotations

__jax_free__ = True

import contextlib
import dataclasses
import logging
import re
from typing import Iterator, List, Optional

__all__ = ["GuardViolation", "GuardStats", "track_compiles",
           "compile_budget"]


class GuardViolation(AssertionError):
    """A guarded region exceeded its declared compile/transfer budget."""


@dataclasses.dataclass
class GuardStats:
    lowerings: List[str] = dataclasses.field(default_factory=list)
    backend_compiles: List[str] = dataclasses.field(default_factory=list)
    cache_hits: int = 0
    cache_misses: int = 0
    device_puts: int = 0
    device_gets: int = 0

    @property
    def compiles(self) -> int:
        return len(self.lowerings)

    def summary(self) -> str:
        names = ", ".join(self.lowerings[:8]) or "-"
        if len(self.lowerings) > 8:
            names += ", ... (%d total)" % len(self.lowerings)
        return ("%d compile(s) [%s], %d backend compile(s), "
                "%d cache hit(s)/%d miss(es), %d device_put, "
                "%d device_get"
                % (self.compiles, names, len(self.backend_compiles),
                   self.cache_hits, self.cache_misses,
                   self.device_puts, self.device_gets))


_COMPILING_RE = re.compile(r"Compiling (\S+)")
_FINISHED_RE = re.compile(r"Finished XLA compilation of (\S+)")
# persistent-cache probe records: the hit comes from the cache backend
# ("Cache hit for key: ..."), the authoritative miss from the compiler
# ("PERSISTENT COMPILATION CACHE MISS ..." — the backend also logs a
# lowercase "Cache miss for key" for the same probe, which is ignored
# so a miss counts once)
_CACHE_HIT_RE = re.compile(r"Cache hit for key")
_CACHE_MISS_RE = re.compile(r"PERSISTENT COMPILATION CACHE MISS")
# jax loggers that carry the records (jax 0.4.x: lowering logs from
# interpreters.pxla, backend-compile timing from dispatch, persistent-
# cache probes from lru_cache/compiler)
_LOGGER_NAMES = ("jax._src.interpreters.pxla", "jax._src.dispatch",
                 "jax._src.lru_cache", "jax._src.compiler")


class _CaptureHandler(logging.Handler):
    def __init__(self, stats: GuardStats):
        super().__init__(level=logging.DEBUG)
        self._stats = stats

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        m = _COMPILING_RE.search(msg)
        if m:
            self._stats.lowerings.append(m.group(1))
            return
        m = _FINISHED_RE.search(msg)
        if m:
            self._stats.backend_compiles.append(m.group(1))
            return
        if _CACHE_HIT_RE.search(msg):
            self._stats.cache_hits += 1
        elif _CACHE_MISS_RE.search(msg):
            self._stats.cache_misses += 1


@contextlib.contextmanager
def track_compiles(
        transfer_guard: Optional[str] = None) -> Iterator[GuardStats]:
    """Count XLA compiles (and explicit transfers) in a with-block.

    transfer_guard: forwarded to jax.transfer_guard for the scope
    ("log", "disallow", ...); None leaves the transfer policy alone.
    """
    import jax

    stats = GuardStats()
    handler = _CaptureHandler(stats)
    prev_flag = bool(jax.config.jax_log_compiles)
    jax.config.update("jax_log_compiles", True)
    touched: List[logging.Logger] = []
    prev_levels: List[int] = []
    prev_propagate: List[bool] = []
    for name in _LOGGER_NAMES:
        lg = logging.getLogger(name)
        touched.append(lg)
        prev_levels.append(lg.level)
        prev_propagate.append(lg.propagate)
        if lg.level > logging.DEBUG or lg.level == logging.NOTSET:
            lg.setLevel(logging.DEBUG)
        # keep the forced compile logging out of the user's stderr: the
        # records exist for the counter, not for display
        lg.propagate = False
        lg.addHandler(handler)

    real_put, real_get = jax.device_put, jax.device_get

    def counting_put(*args: object, **kw: object) -> object:
        stats.device_puts += 1
        return real_put(*args, **kw)

    def counting_get(*args: object, **kw: object) -> object:
        stats.device_gets += 1
        return real_get(*args, **kw)

    jax.device_put, jax.device_get = counting_put, counting_get
    try:
        if transfer_guard is not None:
            with jax.transfer_guard(transfer_guard):
                yield stats
        else:
            yield stats
    finally:
        jax.device_put, jax.device_get = real_put, real_get
        for lg, lv, pr in zip(touched, prev_levels, prev_propagate):
            lg.removeHandler(handler)
            lg.setLevel(lv)
            lg.propagate = pr
        jax.config.update("jax_log_compiles", prev_flag)


@contextlib.contextmanager
def compile_budget(max_compiles: int, *,
                   max_device_puts: Optional[int] = None,
                   max_device_gets: Optional[int] = None,
                   transfer_guard: Optional[str] = None,
                   what: str = "guarded region") -> Iterator[GuardStats]:
    """track_compiles + assertion: more than `max_compiles` lowerings
    (or transfers past their optional budgets) raises GuardViolation
    naming the offending executables."""
    with track_compiles(transfer_guard=transfer_guard) as stats:
        yield stats
    if stats.compiles > max_compiles:
        raise GuardViolation(
            "%s: %d XLA compile(s), budget %d — %s"
            % (what, stats.compiles, max_compiles, stats.summary()))
    if max_device_puts is not None and stats.device_puts > max_device_puts:
        raise GuardViolation(
            "%s: %d jax.device_put call(s), budget %d"
            % (what, stats.device_puts, max_device_puts))
    if max_device_gets is not None and stats.device_gets > max_device_gets:
        raise GuardViolation(
            "%s: %d jax.device_get call(s), budget %d"
            % (what, stats.device_gets, max_device_gets))


try:  # pytest is optional at runtime; the fixture only exists for tests
    import pytest as _pytest
except ImportError:  # pragma: no cover - production image without pytest
    _pytest = None  # type: ignore[assignment]

if _pytest is not None:
    @_pytest.fixture
    def xla_guard() -> object:
        """`with xla_guard(0, what="serving steady state"): ...` — the
        compile_budget context manager as a fixture, so tests declare
        compile budgets without importing the analysis package."""
        return compile_budget

    @_pytest.fixture
    def collective_trace() -> object:
        """`with collective_trace() as events: ...` — the per-rank
        host-collective ring buffer (parallel/dist.trace_collectives)
        as a fixture, same pattern as xla_guard.  Each event is a
        (name, shape, dtype, callsite) CollectiveEvent."""
        from ..parallel.dist import trace_collectives
        return trace_collectives
