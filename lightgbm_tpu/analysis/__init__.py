"""Static analysis + runtime guards for the project's hot-path invariants.

The codebase carries several load-bearing invariants that no ordinary
test exercises directly — they hold by construction until someone edits
the wrong line, and then they regress silently:

  * the native `task=predict` fast path and the CLI arg-parse never
    import jax (predict_fast.py docstring: the JAX startup cost is one
    the reference binary never pays);
  * device code never host-syncs mid-trace and never touches float64
    (x64 is off during training; bit-parity with the reference is the
    whole point, PARITY.md);
  * the serving forest never recompiles in steady state (the
    power-of-two pre-compile contract, serving/forest.py);
  * serving shared state mutates only under its lock.

This package machine-checks them:

  graftlint.py  AST linter (`python -m lightgbm_tpu.analysis`), ~12
                per-module rules with verified inline suppressions.
                Pure stdlib — runs without jax.
  contracts.py  the contract registry: invariants DECLARED at the
                definition site (@contract.traced_pure, .parity_oracle,
                .jax_free, .locked_by, .fused_body, .counted_flush and
                the `__jax_free__` module marker), zero-cost at runtime.
  callgraph.py  package-wide symbol table + call graph: module/import
                resolution, method binding, closures, factories.
  graftcheck.py whole-program contract analysis (rules GC001-GC008):
                taint/effect propagation ACROSS calls — a host sync
                three helpers below a traced entry point, a transitive
                jax import two hops below a jax-free module, a serving
                mutator reachable from an unlocked public method.
  graftsync.py  SPMD collective-safety analysis (rules GC009-GC011):
                host-collective SEQUENCES identical across ranks —
                rank-gated/reordered collectives, collective loops
                with rank-local trip counts, multihost calls outside
                parallel/dist.py.  The runtime side lives in
                parallel/dist.trace_collectives.
  lockgraph.py  lock-order analysis (rule GC012): acquisition cycles
                and blocking operations (cold loads, dispatch, socket
                I/O) under fast serving locks.
  mutations.py  seeded-violation corpus: deliberate contract breaks
                applied as source transforms to copies of the real
                modules, proving every rule catches its bug class
                (tests/test_graftcheck_mutations.py).
  typegate.py   annotation-completeness gate for the mypy-strict
                modules (config.py, api.py, serving/, analysis/) so
                the typing bar holds even on machines without mypy.
  guards.py     runtime counters: XLA compile + explicit-transfer
                accounting as a context manager and pytest fixture,
                so tests can assert "zero recompiles" budgets.

See README.md "Static analysis & invariants" for the rule table and
suppression syntax, and CONTRACTS.md for the contract registry.
"""

__jax_free__ = True

__all__ = ["run_graftlint", "run_graftcheck", "run_typegate", "contract",
           "compile_budget", "track_compiles", "GuardViolation"]


def __getattr__(name: str) -> object:
    # PEP 562: keep `import lightgbm_tpu.analysis` light
    if name == "run_graftlint":
        from .graftlint import run_graftlint
        return run_graftlint
    if name == "run_graftcheck":
        from .graftcheck import run_graftcheck
        return run_graftcheck
    if name == "contract":
        from .contracts import contract
        return contract
    if name == "run_typegate":
        from .typegate import run_typegate
        return run_typegate
    if name in ("compile_budget", "track_compiles", "GuardViolation"):
        from . import guards
        return getattr(guards, name)
    raise AttributeError(name)
