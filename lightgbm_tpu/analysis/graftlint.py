"""graftlint — the project-specific AST linter.

Each rule guards one invariant the test suite cannot see directly (the
code works today; the rule keeps the NEXT edit from silently breaking
the performance or parity story).  Pure stdlib: no jax import, so the
linter runs in any environment, including the jax-free fast-path CI
lanes it protects.

Rules (README.md "Static analysis & invariants" has the full table):

  GL001 host-sync-in-traced-fn     `.item()`, `float()/int()/bool()` on
        traced values, `np.asarray`/`np.array`, `jax.device_get/put`
        inside jit-traced functions — each is a silent host round-trip
        that serializes the device pipeline.
  GL002 jax-import-in-jax-free-module  module-level `import jax` (or a
        module-level import of a non-jax-free package module) in the
        contractually jax-free import paths (predict_fast, cli,
        io/parser, serving fallback, ...).
  GL003 float64-in-device-code     explicit float64 dtypes inside traced
        functions: x64 is off, so these either fail or silently demote
        — and under x64 they would fork the executable from the f32
        parity configuration.
  GL004 jit-missing-static         jit-wrapped functions whose
        configuration-like parameters (keyword-only, or str/bool/int
        annotated or defaulted) are not in static_argnames/nums: each
        distinct value would retrace instead of re-specializing.
  GL005 wallclock-or-rng-in-parity-path  `time.*` / `random` /
        `np.random` in parity-load-bearing modules — all randomness
        must come from utils/mt19937 (the reference's stream) and no
        value may depend on the clock.
  GL006 unlocked-serving-mutation  `self.*` attribute stores in
        serving/ outside __init__ and outside a `with <...lock/cv>`
        block (attribute heuristic; suppressions document the
        intentionally lock-free writes).
  GL007 global-jax-config-mutation jax.config.update of process-wide
        knobs (x64, platforms, ...) outside the process-owning entry
        points (cli.py, __main__.py): a library import must never
        reconfigure its host process.
  GL008 stdout-bypasses-logger     print()/sys.stdout outside
        utils/log.py and cli.py: training-log parity diffs against the
        reference depend on every line going through the logger.
  GL009 suppression-missing-justification  `# graftlint: disable=` with
        no (or a trivial) `-- why` justification.
  GL010 unused-suppression         a disable comment whose rule did not
        actually fire on that line — stale suppressions rot.
  GL011 static-bag-shape           a bag-count/bag-size name treated as
        a TRACED value: `int()`/`.item()` on one inside a traced
        function, or a bag-size parameter of a jitted signature missing
        from static_argnames.  Bag counts are deterministic (mt19937
        host draws; config.bag_compact ceil_pads them into static
        windows), so they are SHAPE inputs — tracing one would retrace
        the fused step at every re-bagging epoch.
  GL012 host-sync-in-scan-carry    `.item()` / `int()`/`float()`/
        `bool()` / `np.asarray` / `jax.device_get` on a scan carry or
        per-iteration value inside a lax.scan body — the iteration-
        batched training loop (config.iter_batch) exists to remove the
        per-iteration host round-trip, and a host sync inside the scan
        body is a tracer error at best and a silent K-fold serialization
        at worst.  Wins over GL001 inside scan bodies (GL011 still wins
        for bag counts).

Suppression syntax (GL009/GL010 verify it):

    expr  # graftlint: disable=GL003 -- f64 is the contract here: ...

The justification after `--` must be non-trivial (>= 20 chars).  A
suppression applies to findings anchored on its own line, or — when
the comment is on a line of its own — to the line directly below.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__jax_free__ = True

RULES: Dict[str, str] = {
    "GL001": "host-sync-in-traced-fn",
    "GL002": "jax-import-in-jax-free-module",
    "GL003": "float64-in-device-code",
    "GL004": "jit-missing-static",
    "GL005": "wallclock-or-rng-in-parity-path",
    "GL006": "unlocked-serving-mutation",
    "GL007": "global-jax-config-mutation",
    "GL008": "stdout-bypasses-logger",
    "GL009": "suppression-missing-justification",
    "GL010": "unused-suppression",
    "GL011": "static-bag-shape",
    "GL012": "host-sync-in-scan-carry",
}

# id -> human name for EVERY rule family that renders through Finding;
# graftcheck registers its GC0xx whole-program rules here on import
RULE_NAMES: Dict[str, str] = dict(RULES)

# lax.scan-family transforms whose body argument is a scan body (GL012:
# host syncs there serialize every batched iteration, not just one)
_SCAN_NAMES = {
    "jax.lax.scan", "lax.scan",
    "jax.lax.associative_scan", "lax.associative_scan",
}

# Names that hold a bag count / compacted-window size (the static-bag-
# shape contract, GL011).  Deliberately does NOT match bag_mask/bag_masks
# — masks are genuine traced row data; it is the COUNTS that are shapes.
BAG_SIZE_RE = re.compile(
    r"(^|_)(bag|compact)_?(rows|cnt|count|size|window)($|_)",
    re.IGNORECASE)

# Rules about the suppression mechanism itself can never be suppressed.
UNSUPPRESSABLE = {"GL009", "GL010"}

# ---------------------------------------------------------------------------
# Module sets (paths relative to the package root, posix separators)
# ---------------------------------------------------------------------------

# Modules that must stay importable without jax anywhere in sys.modules
# (the native task=predict fast path, CLI arg-parse, IO, the serving
# fallback engine, this analysis package itself) DECLARE themselves with
# a module-level `__jax_free__ = True` marker — the set is DISCOVERED
# per run (_discover_jax_free), not hard-coded, so a new serving/io
# module cannot silently escape the gate (graftcheck GC007 additionally
# requires an explicit declaration under contracts.DECLARE_DIRS).  At
# module level a marked module may import jax/jaxlib neither directly
# nor transitively (via a package module outside the marked set);
# function-local imports are the sanctioned lazy pattern.
_JAX_FREE_MARKER = "__jax_free__"
# cheap pre-filter only — the authoritative check is the AST walk below
# (a column-0 example line inside a docstring must NOT count)
_MARKER_HINT_RE = re.compile(r"^__jax_free__", re.MULTILINE)


def _tree_declares_jax_free(tree: ast.Module) -> Optional[bool]:
    """The module's `__jax_free__` declaration from its AST (module
    level, if/try blocks included like any import-time statement —
    but NOT docstring text or function-local assignments)."""
    stack: List[ast.stmt] = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, ast.If):
            stack.extend(node.body)
            stack.extend(node.orelse)
        elif isinstance(node, ast.Try):
            stack.extend(node.body)
            stack.extend(node.orelse)
            stack.extend(node.finalbody)
            for h in node.handlers:
                stack.extend(h.body)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == _JAX_FREE_MARKER \
                        and isinstance(node.value, ast.Constant) \
                        and isinstance(node.value.value, bool):
                    return node.value.value
    return None


def _source_declares_jax_free(source: str) -> Optional[bool]:
    """The module's own `__jax_free__` declaration, if any.  AST-based
    (matching analysis/callgraph.py), with a regex pre-filter so the
    package-wide discovery scan stays cheap."""
    if _MARKER_HINT_RE.search(source) is None:
        return None
    try:
        return _tree_declares_jax_free(ast.parse(source))
    except SyntaxError:
        return None

# Modules whose output must be bit-reproducible against the reference
# binary: no wall clock, no RNG outside utils/mt19937.
PARITY_MODULES: Set[str] = {
    "objectives.py", "metrics.py", "predict_fast.py",
    "models/gbdt.py", "models/tree.py",
    "io/parser.py", "io/binning.py", "io/dataset.py",
    "native/__init__.py", "utils/mt19937.py",
    "parallel/mesh.py", "parallel/dist.py",
    # out-of-core ingest: shard bytes must equal the in-memory
    # loader's bins bit-for-bit (synth.py is OUT on purpose — it
    # generates random benchmark data, not parity artifacts)
    "ingest/manifest.py", "ingest/writer.py", "ingest/shards.py",
}
PARITY_PREFIXES = ("ops/",)

SERVING_PREFIX = "serving/"

# Process-owning entry points may mutate global jax config (GL007) —
# and utils/device.py, the one resolver every entry point calls to
# turn device_type into a platform.
ENTRY_MODULES = {"cli.py", "__main__.py", "utils/device.py"}

# The logger's home (and the CLI's stderr error report) may write to
# stdio directly (GL008).
STDIO_EXEMPT = {"utils/log.py", "cli.py"}

# jax.config keys whose process-wide mutation GL007 flags.  The
# compilation-cache keys are deliberately absent: utils/compile_cache
# exists to set them, and they do not change numerics or tracing.
GLOBAL_JAX_KNOBS = {
    "jax_enable_x64", "jax_platforms", "jax_default_matmul_precision",
    "jax_disable_jit", "jax_numpy_dtype_promotion",
}

# Functions whose RETURNED closures are device code by project
# convention (objective gradient factories; the fused-step makers are
# caught structurally via jax.jit/shard_map dataflow).
TRACED_FACTORY_NAMES = re.compile(
    r"^(make_grad_fn|make_row_state_fn|_fused_step\w*|fused_step\w*)$")

_JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit"}
_TRACE_TRANSFORMS = {
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.map", "jax.lax.cond", "jax.lax.switch",
    "jax.lax.associative_scan", "lax.scan", "lax.while_loop",
    "lax.fori_loop", "lax.map", "lax.cond", "lax.switch",
    "jax.vmap", "vmap", "jax.grad", "jax.value_and_grad",
    "shard_map", "jax.shard_map",
    "pl.pallas_call", "pallas_call", "jax.checkpoint", "jax.remat",
}
_HOST_SYNC_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "np.ascontiguousarray", "numpy.ascontiguousarray",
    "np.frombuffer", "numpy.frombuffer",
    "jax.device_get", "jax.device_put",
}
_SHAPEISH_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize"}
_F64_ATTRS = {"jnp.float64", "np.float64", "numpy.float64",
              "jax.numpy.float64"}
_TIME_ATTRS = {"time", "perf_counter", "monotonic", "sleep",
               "process_time", "perf_counter_ns", "time_ns",
               "monotonic_ns"}

MIN_JUSTIFICATION_CHARS = 20

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Z0-9,\s]+?)\s*(?:--\s*(.*))?$")


@dataclasses.dataclass
class Finding:
    path: str          # path as given (package-relative for the package walk)
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return "%s:%d: %s [%s] %s" % (
            self.path, self.line, self.rule,
            RULE_NAMES.get(self.rule, "typing"), self.message)


@dataclasses.dataclass
class Suppression:
    line: int          # the line the comment sits on
    rules: Tuple[str, ...]
    justification: str
    own_line: bool     # comment-only line: applies to the line below
    # staleness is PER RULE: disable=GL003,GL006 where only GL003 fires
    # must still report the GL006 half as stale
    used_rules: Set[str] = dataclasses.field(default_factory=set)


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _names_bag_size(node: ast.AST) -> bool:
    """Does this expression reference a bag-count/bag-size name (GL011)?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and BAG_SIZE_RE.search(sub.id):
            return True
        if isinstance(sub, ast.Attribute) \
                and BAG_SIZE_RE.search(sub.attr):
            return True
    return False


def _attach_parents(tree: ast.AST) -> None:
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            child._gl_parent = parent  # type: ignore[attr-defined]


def _enclosing_functions(node: ast.AST) -> Iterable[ast.AST]:
    cur = getattr(node, "_gl_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            yield cur
        cur = getattr(cur, "_gl_parent", None)


def _all_params(fn: ast.AST) -> List[ast.arg]:
    a = fn.args
    return list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)


def _const_str_tuple(node: ast.AST) -> Tuple[str, ...]:
    """static_argnames value -> names (string or tuple/list of strings)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
        return tuple(out)
    return ()


def _const_int_tuple(node: ast.AST) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(el.value for el in node.elts
                     if isinstance(el, ast.Constant)
                     and isinstance(el.value, int))
    return ()


# ---------------------------------------------------------------------------
# Trace analysis: which functions run under jit?
# ---------------------------------------------------------------------------

class _TraceIndex:
    """Classifies every function in a module as traced / host.

    Traced roots:
      * defs decorated @jax.jit / @functools.partial(jax.jit, ...)
      * local defs passed (by name) to jax.jit(...) / shard_map /
        jax.lax.* / pallas_call — directly or through a local variable
      * closures RETURNED by a "factory": a local def whose call result
        flows into jax.jit/shard_map (the fused-step makers), or whose
        name matches TRACED_FACTORY_NAMES (objective grad factories)
    Propagation: every def nested inside a traced def is traced.
    """

    def __init__(self, tree: ast.AST):
        self.defs: List[ast.AST] = [
            n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        self.by_name: Dict[str, List[ast.AST]] = {}
        for d in self.defs:
            self.by_name.setdefault(d.name, []).append(d)
        self.traced: Set[ast.AST] = set()
        self.scan_bodies: Set[ast.AST] = set()
        self.statics: Dict[ast.AST, Set[str]] = {}
        self.jit_roots: List[Tuple[ast.AST, Set[str]]] = []
        self._factories: Set[ast.AST] = set()
        self._collect(tree)
        self._propagate()

    # -- collection ----------------------------------------------------
    def _jit_call_statics(self, call: ast.Call,
                          target: Optional[ast.AST]) -> Set[str]:
        names = set()
        nums: Tuple[int, ...] = ()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                names.update(_const_str_tuple(kw.value))
            elif kw.arg == "static_argnums":
                nums = _const_int_tuple(kw.value)
        if target is not None and nums:
            params = _all_params(target)
            for i in nums:
                if 0 <= i < len(params):
                    names.add(params[i].arg)
        return names

    def _mark_traced(self, fn: ast.AST, statics: Set[str],
                     jit_root: bool) -> None:
        self.traced.add(fn)
        self.statics.setdefault(fn, set()).update(statics)
        if jit_root:
            self.jit_roots.append((fn, statics))

    def _local_def_from_expr(self, node: ast.AST,
                             assigned: Dict[str, List[ast.AST]]
                             ) -> List[ast.AST]:
        """Local defs whose call result `node` evaluates to (handles
        f(...), name-assigned-from-f(...), and conditional expressions
        over those)."""
        if isinstance(node, ast.IfExp):
            return (self._local_def_from_expr(node.body, assigned)
                    + self._local_def_from_expr(node.orelse, assigned))
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name is None and isinstance(node.func, ast.Attribute):
                # self.make_grad_fn() style: resolve by method name
                return self.by_name.get(node.func.attr, [])
            if name is not None:
                base = name.split(".")[-1]
                return self.by_name.get(base, [])
        if isinstance(node, ast.Name):
            return assigned.get(node.id, [])
        return []

    def _collect(self, tree: ast.AST) -> None:
        # decorator-based roots
        for d in self.defs:
            for dec in d.decorator_list:
                if isinstance(dec, ast.Call):
                    name = _dotted(dec.func)
                    if name in _JIT_NAMES:
                        self._mark_traced(
                            d, self._jit_call_statics(dec, d), True)
                    elif name in ("functools.partial", "partial"):
                        if dec.args and _dotted(dec.args[0]) in _JIT_NAMES:
                            self._mark_traced(
                                d, self._jit_call_statics(dec, d), True)
                    elif name in _TRACE_TRANSFORMS:
                        self._mark_traced(d, set(), False)
                else:
                    if _dotted(dec) in _JIT_NAMES:
                        self._mark_traced(d, set(), True)

        # name -> local defs whose call result the name holds
        assigned: Dict[str, List[ast.AST]] = {}
        for n in ast.walk(tree):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                defs = self._local_def_from_expr(n.value, {})
                if defs:
                    assigned[n.targets[0].id] = defs

        for n in ast.walk(tree):
            if not isinstance(n, ast.Call):
                continue
            name = _dotted(n.func)
            if name in _JIT_NAMES and n.args:
                arg0 = n.args[0]
                if isinstance(arg0, ast.Lambda):
                    self._mark_traced(arg0, set(), True)
                elif isinstance(arg0, ast.Name):
                    hit = False
                    for d in self.by_name.get(arg0.id, []):
                        self._mark_traced(
                            d, self._jit_call_statics(n, d), True)
                        hit = True
                    if not hit:
                        for d in self._local_def_from_expr(arg0, assigned):
                            self._factories.add(d)
                else:
                    for d in self._local_def_from_expr(arg0, assigned):
                        self._factories.add(d)
            elif name in _TRACE_TRANSFORMS:
                for arg in list(n.args) + [kw.value for kw in n.keywords]:
                    if isinstance(arg, ast.Lambda):
                        self._mark_traced(arg, set(), False)
                    elif isinstance(arg, ast.Name):
                        for d in self.by_name.get(arg.id, []):
                            self._mark_traced(d, set(), False)
                        for d in self._local_def_from_expr(arg, assigned):
                            self._factories.add(d)
                    elif isinstance(arg, ast.Call):
                        for d in self._local_def_from_expr(arg, assigned):
                            self._factories.add(d)
                if name in _SCAN_NAMES and n.args:
                    # the FIRST argument is the scan body: host syncs on
                    # its carry/xs serialize every batched iteration
                    # (GL012).  Resolve the name LEXICALLY — prefer defs
                    # in the scan call's own enclosing functions, then
                    # module level — so an unrelated same-named def
                    # elsewhere (`def body` is a common inner-fn name)
                    # is not misclassified as a scan body.
                    body = n.args[0]
                    if isinstance(body, ast.Lambda):
                        self.scan_bodies.add(body)
                    elif isinstance(body, ast.Name):
                        cands = self.by_name.get(body.id, [])
                        encl = set(_enclosing_functions(n))
                        scoped = [d for d in cands
                                  if getattr(d, "_gl_parent", None)
                                  in encl]
                        if not scoped:
                            scoped = [d for d in cands if isinstance(
                                getattr(d, "_gl_parent", None),
                                ast.Module)]
                        self.scan_bodies.update(scoped or cands)

        for d in self.defs:
            if TRACED_FACTORY_NAMES.match(d.name):
                self._factories.add(d)

        # factories: their returned local closures are traced
        for f in self._factories:
            inner_names = {d.name for d in self.defs
                           if getattr(d, "_gl_parent", None) is f
                           or self._nested_in(d, f)}
            for ret in ast.walk(f):
                if isinstance(ret, ast.Return) and ret.value is not None:
                    for t in self._returned_closures(ret.value, inner_names):
                        self._mark_traced(t, set(), False)

    def _returned_closures(self, node: ast.AST,
                           inner_names: Set[str]) -> List[ast.AST]:
        if isinstance(node, ast.IfExp):
            return (self._returned_closures(node.body, inner_names)
                    + self._returned_closures(node.orelse, inner_names))
        if isinstance(node, ast.Lambda):
            return [node]
        if isinstance(node, ast.Name) and node.id in inner_names:
            return self.by_name.get(node.id, [])
        return []

    @staticmethod
    def _nested_in(d: ast.AST, f: ast.AST) -> bool:
        cur = getattr(d, "_gl_parent", None)
        while cur is not None:
            if cur is f:
                return True
            cur = getattr(cur, "_gl_parent", None)
        return False

    def _propagate(self) -> None:
        changed = True
        while changed:
            changed = False
            for d in self.defs:
                for anc in _enclosing_functions(d):
                    if anc in self.traced and d not in self.traced:
                        self.traced.add(d)
                        changed = True
                    if anc in self.scan_bodies \
                            and d not in self.scan_bodies:
                        # nested helpers inside a scan body inherit its
                        # carry discipline (GL012)
                        self.scan_bodies.add(d)
                        changed = True

    def is_traced(self, node: ast.AST) -> bool:
        """Is this (non-def) node's innermost enclosing function traced?"""
        for fn in _enclosing_functions(node):
            return fn in self.traced
        return False

    def in_scan_body(self, node: ast.AST) -> bool:
        """Is this node's innermost enclosing function a lax.scan body
        (or nested inside one)?"""
        for fn in _enclosing_functions(node):
            return fn in self.scan_bodies
        return False

    def innermost(self, node: ast.AST) -> Optional[ast.AST]:
        for fn in _enclosing_functions(node):
            return fn
        return None


# ---------------------------------------------------------------------------
# Per-function taint: which names hold traced values?
# ---------------------------------------------------------------------------

def _expr_tainted(node: ast.AST, tainted: Set[str]) -> bool:
    """Does this expression reference a traced value other than through
    shape/ndim/dtype metadata or len()?"""
    if isinstance(node, ast.Attribute) and node.attr in _SHAPEISH_ATTRS:
        return False
    if isinstance(node, ast.Call):
        fname = _dotted(node.func)
        if fname == "len":
            return False
        # a call can launder taint through a function; stay conservative
        # only for direct name args
    if isinstance(node, ast.Name):
        return node.id in tainted
    for child in ast.iter_child_nodes(node):
        if _expr_tainted(child, tainted):
            return True
    return False


def _function_taint(fn: ast.AST, statics: Set[str]) -> Set[str]:
    tainted: Set[str] = set()
    if isinstance(fn, ast.Lambda):
        params = list(fn.args.posonlyargs) + list(fn.args.args) \
            + list(fn.args.kwonlyargs)
    else:
        params = _all_params(fn)
    for i, p in enumerate(params):
        if i == 0 and p.arg in ("self", "cls"):
            continue
        if p.arg in statics:
            continue
        tainted.add(p.arg)
    if isinstance(fn, ast.Lambda):
        return tainted
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign) and _expr_tainted(n.value, tainted):
            for t in n.targets:
                for leaf in ast.walk(t):
                    if isinstance(leaf, ast.Name):
                        tainted.add(leaf.id)
    return tainted


# ---------------------------------------------------------------------------
# The linter
# ---------------------------------------------------------------------------

class ModuleLint:
    def __init__(self, relpath: str, source: str, display_path: str):
        self.rel = relpath.replace(os.sep, "/")
        self.display = display_path
        self.source = source
        self.findings: List[Finding] = []
        self.tree = ast.parse(source, filename=display_path)
        _attach_parents(self.tree)
        self.lines = source.splitlines()
        self.suppressions = self._parse_suppressions()

    # -- suppressions --------------------------------------------------
    def _parse_suppressions(self) -> List[Suppression]:
        """Real COMMENT tokens only (a suppression example inside a
        docstring must not count)."""
        import io
        import tokenize
        out = []
        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(self.source).readline))
        except (tokenize.TokenError, IndentationError):
            tokens = []
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            i = tok.start[0]
            rules = tuple(r.strip() for r in m.group(1).split(",")
                          if r.strip())
            just = (m.group(2) or "").strip()
            own = self.lines[i - 1].lstrip().startswith("#")
            out.append(Suppression(i, rules, just, own))
        return out

    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        self.findings.append(
            Finding(self.display, getattr(node, "lineno", 1), rule,
                    message))

    # -- GL001 / GL003 / GL004 (trace-aware rules) ----------------------
    def check_traced(self) -> None:
        idx = _TraceIndex(self.tree)
        taint_cache: Dict[ast.AST, Set[str]] = {}

        def taint_for(fn: ast.AST) -> Set[str]:
            got = taint_cache.get(fn)
            if got is None:
                got = _function_taint(fn, idx.statics.get(fn, set()))
                taint_cache[fn] = got
            return got

        for n in ast.walk(self.tree):
            fn = idx.innermost(n)
            if fn is None or fn not in idx.traced:
                continue
            # inside a lax.scan body the host-sync rules sharpen to
            # GL012: the sync lands on a scan carry / per-iteration
            # value and serializes EVERY batched iteration (GL011's
            # bag-count classification still wins)
            sync_rule = "GL012" if idx.in_scan_body(n) else "GL001"
            if isinstance(n, ast.Call):
                name = _dotted(n.func)
                if isinstance(n.func, ast.Attribute) \
                        and n.func.attr == "item" and not n.args:
                    if _names_bag_size(n.func.value):
                        self._emit(n, "GL011",
                                   ".item() on a bag count inside a "
                                   "traced function: bag counts are "
                                   "STATIC shapes (host mt19937 draws, "
                                   "ceil_padded windows) — keep them "
                                   "Python ints outside the trace")
                    elif sync_rule == "GL012":
                        self._emit(n, "GL012",
                                   ".item() on a scan carry/per-"
                                   "iteration value inside a scanned "
                                   "training-loop body — host sync "
                                   "serializes every batched iteration")
                    else:
                        self._emit(n, "GL001",
                                   ".item() forces a device->host sync "
                                   "inside a traced function")
                elif name in _HOST_SYNC_CALLS:
                    if sync_rule == "GL012":
                        self._emit(n, "GL012",
                                   "%s inside a lax.scan body is a host "
                                   "sync on scan state — it would "
                                   "serialize every iteration of the "
                                   "batched training loop" % name)
                    else:
                        self._emit(n, "GL001",
                                   "%s inside a traced function is a "
                                   "host round-trip (use jnp / keep it "
                                   "outside the trace)" % name)
                elif name in ("float", "int", "bool") and len(n.args) == 1:
                    if _expr_tainted(n.args[0], taint_for(fn)):
                        if _names_bag_size(n.args[0]):
                            self._emit(n, "GL011",
                                       "%s() on a traced bag count: bag "
                                       "counts are STATIC shapes — "
                                       "compute them on the host and "
                                       "close over them (or pass via "
                                       "static_argnames)" % name)
                        elif sync_rule == "GL012":
                            self._emit(n, "GL012",
                                       "%s() on a scan carry/per-"
                                       "iteration value concretizes it "
                                       "inside the scanned training "
                                       "loop (tracer error / K-fold "
                                       "host sync)" % name)
                        else:
                            self._emit(n, "GL001",
                                       "%s() on a traced value "
                                       "concretizes it (host sync / "
                                       "tracer error)" % name)
            # float64 mentions in device code
            if isinstance(n, ast.Attribute) \
                    and _dotted(n) in _F64_ATTRS:
                self._emit(n, "GL003",
                           "explicit float64 in device code (x64 is "
                           "off; f32 is the parity configuration)")
            if isinstance(n, ast.Constant) and n.value == "float64":
                parent = getattr(n, "_gl_parent", None)
                if isinstance(parent, ast.keyword) \
                        and parent.arg == "dtype":
                    self._emit(n, "GL003",
                               'dtype="float64" in device code (x64 is '
                               "off; f32 is the parity configuration)")

        # GL004: configuration-like params must be static
        for fn, statics in idx.jit_roots:
            if isinstance(fn, ast.Lambda):
                continue
            params = _all_params(fn)
            kwonly = {p.arg for p in fn.args.kwonlyargs}
            defaults: Dict[str, ast.AST] = {}
            pos = list(fn.args.posonlyargs) + list(fn.args.args)
            for p, d in zip(pos[len(pos) - len(fn.args.defaults):],
                            fn.args.defaults):
                defaults[p.arg] = d
            for p, d in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
                if d is not None:
                    defaults[p.arg] = d
            for i, p in enumerate(params):
                if i == 0 and p.arg in ("self", "cls"):
                    continue
                if p.arg in statics:
                    continue
                if BAG_SIZE_RE.search(p.arg):
                    # the static-bag-shape contract: a bag-size argument
                    # reaching a jitted signature non-statically would
                    # retrace the executable at every re-bagging epoch
                    self._emit(
                        fn, "GL011",
                        "jit of %r: bag-size parameter %r is not in "
                        "static_argnames — the compacted window must be "
                        "a static shape (zero recompiles across "
                        "re-bagging boundaries)" % (fn.name, p.arg))
                    continue
                confy = p.arg in kwonly
                d = defaults.get(p.arg)
                if isinstance(d, ast.Constant) \
                        and isinstance(d.value, (str, bool)):
                    confy = True
                ann = getattr(p, "annotation", None)
                if isinstance(ann, ast.Name) \
                        and ann.id in ("str", "bool", "int"):
                    confy = True
                if confy:
                    self._emit(
                        fn, "GL004",
                        "jit of %r: parameter %r looks configuration-"
                        "like but is not in static_argnames — every "
                        "distinct value will retrace"
                        % (fn.name, p.arg))

    # -- GL002 ----------------------------------------------------------
    def _declares_jax_free(self) -> bool:
        """This module's own declaration wins; otherwise the discovered
        package-wide marker set (so lint_source() of an in-memory
        module at a real path sees the installed module's contract)."""
        own = _tree_declares_jax_free(self.tree)
        if own is not None:
            return own
        return self.rel in _JAX_FREE

    def check_jax_free(self) -> None:
        if not self._declares_jax_free():
            return
        pkg_dir = os.path.dirname(self.rel)  # "" for top-level modules
        pkg_name = os.path.basename(package_root())

        def resolve(level: int, module: Optional[str]) -> Optional[str]:
            """Import -> package-relative module path (or None for
            out-of-package imports).  Handles both the relative form
            (level > 0) and the absolute `lightgbm_tpu.x.y` form."""
            if level == 0:
                mod = module or ""
                if mod == pkg_name:
                    return ""
                if mod.startswith(pkg_name + "."):
                    return mod[len(pkg_name) + 1:].replace(".", "/")
                return None
            base = pkg_dir
            for _ in range(level - 1):
                base = os.path.dirname(base)
            mod = (module or "").replace(".", "/")
            return ("%s/%s" % (base, mod)).strip("/") if mod else base

        def target_ok(path: Optional[str], names: Sequence[str]) -> List[str]:
            """Non-jax-free package modules reached by this import."""
            bad = []
            if path is None:
                return bad
            candidates = []
            if names:
                for nm in names:
                    candidates.append("%s/%s" % (path, nm) if path
                                      else nm)
            mods = candidates + [path]
            for cand in mods:
                for suffix in (cand + ".py", cand + "/__init__.py"):
                    if suffix in _ALL_MODULES:
                        if suffix not in _JAX_FREE:
                            bad.append(suffix)
                        break
            return bad

        def module_level_stmts(
                body: Sequence[ast.stmt]) -> Iterable[ast.stmt]:
            """Module-level statements, descending into `if` blocks (a
            conditionally-guarded import still executes at import time)
            — except TYPE_CHECKING blocks, which never run."""
            for node in body:
                if isinstance(node, ast.If):
                    test = _dotted(node.test)
                    if test in ("TYPE_CHECKING", "typing.TYPE_CHECKING"):
                        # the guarded body never runs — but its ELSE
                        # branch runs in every real process
                        yield from module_level_stmts(node.orelse)
                        continue
                    yield from module_level_stmts(node.body)
                    yield from module_level_stmts(node.orelse)
                elif isinstance(node, ast.Try):
                    yield from module_level_stmts(node.body)
                    yield from module_level_stmts(node.orelse)
                    yield from module_level_stmts(node.finalbody)
                    for h in node.handlers:
                        yield from module_level_stmts(h.body)
                else:
                    yield node

        for node in module_level_stmts(self.tree.body):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    root = alias.name.split(".")[0]
                    if root in ("jax", "jaxlib"):
                        self._emit(node, "GL002",
                                   "module-level `import %s` in a "
                                   "contractually jax-free module"
                                   % alias.name)
                    else:
                        path = resolve(0, alias.name)
                        for bad in target_ok(path, []):
                            self._emit(node, "GL002",
                                       "module-level import of %s, "
                                       "which is not jax-free, from a "
                                       "jax-free module" % bad)
            elif isinstance(node, ast.ImportFrom):
                root = (node.module or "").split(".")[0]
                if node.level == 0 and root in ("jax", "jaxlib"):
                    self._emit(node, "GL002",
                               "module-level `from %s import ...` in a "
                               "contractually jax-free module"
                               % node.module)
                    continue
                path = resolve(node.level, node.module)
                for bad in target_ok(path,
                                     [a.name for a in node.names]):
                    self._emit(node, "GL002",
                               "module-level import of %s, which is "
                               "not jax-free, from a jax-free module"
                               % bad)

    # -- GL005 ----------------------------------------------------------
    def check_parity(self) -> None:
        if self.rel not in PARITY_MODULES \
                and not self.rel.startswith(PARITY_PREFIXES):
            return
        for n in ast.walk(self.tree):
            if isinstance(n, ast.Import):
                for alias in n.names:
                    if alias.name in ("time", "random"):
                        self._emit(n, "GL005",
                                   "`import %s` in a parity-load-"
                                   "bearing module (randomness must "
                                   "come from utils/mt19937; no value "
                                   "may depend on the clock)"
                                   % alias.name)
            elif isinstance(n, ast.ImportFrom):
                if node_mod := (n.module or ""):
                    if node_mod in ("time", "random") and n.level == 0:
                        self._emit(n, "GL005",
                                   "`from %s import ...` in a parity-"
                                   "load-bearing module" % node_mod)
            elif isinstance(n, ast.Attribute):
                name = _dotted(n)
                # match only the base `np.random` attribute node — the
                # inner node of every `np.random.X` chain — so one use
                # emits one finding
                if name in ("np.random", "numpy.random"):
                    self._emit(n, "GL005",
                               "np.random in a parity-load-bearing "
                               "module — use utils/mt19937 (the "
                               "reference's stream)")
                elif name in {"time." + a for a in _TIME_ATTRS}:
                    self._emit(n, "GL005",
                               "%s in a parity-load-bearing module — "
                               "no value may depend on the clock"
                               % name)

    # -- GL006 ----------------------------------------------------------
    def check_serving_locks(self) -> None:
        if not self.rel.startswith(SERVING_PREFIX):
            return

        def lockish(expr: ast.AST) -> bool:
            name = _dotted(expr) or ""
            low = name.lower()
            return "lock" in low or low.endswith("_cv") or "cv" == \
                low.rsplit(".", 1)[-1]

        def under_lock(node: ast.AST) -> bool:
            cur = getattr(node, "_gl_parent", None)
            while cur is not None:
                if isinstance(cur, ast.With):
                    for item in cur.items:
                        ctx = item.context_expr
                        if isinstance(ctx, ast.Call):
                            ctx = ctx.func
                        if lockish(ctx):
                            return True
                if isinstance(cur, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    return False
                cur = getattr(cur, "_gl_parent", None)
            return False

        def has_locked_by_contract(fn: ast.AST) -> bool:
            """@contract.locked_by("...") moves the proof obligation to
            graftcheck GC004: every call path into the function must
            hold the named lock, so per-line suppressions inside it are
            no longer needed (or wanted)."""
            for dec in getattr(fn, "decorator_list", []):
                target = dec.func if isinstance(dec, ast.Call) else dec
                dotted = _dotted(target) or ""
                if dotted.endswith("contract.locked_by"):
                    return True
            return False

        def self_attr_target(t: ast.AST) -> Optional[str]:
            """'a.b.c' when the store target is an attribute chain (or
            a subscript of one — `self.requests[k] = ...` mutates the
            shared dict exactly like a plain store) rooted at `self`,
            else None."""
            while isinstance(t, ast.Subscript):
                t = t.value
            if not isinstance(t, ast.Attribute):
                return None
            name = _dotted(t)
            if name and name.startswith("self."):
                return name
            return None

        for n in ast.walk(self.tree):
            fn = None
            for f in _enclosing_functions(n):
                fn = f
                break
            if fn is None or isinstance(fn, ast.Lambda):
                continue
            if fn.name in ("__init__", "__init_subclass__", "__new__"):
                continue
            if has_locked_by_contract(fn):
                continue
            targets: List[ast.AST] = []
            if isinstance(n, ast.Assign):
                targets = n.targets
            elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
                targets = [n.target]
            else:
                continue
            for t in targets:
                name = self_attr_target(t)
                if name is None:
                    continue
                if "lock" in name.lower() or name.lower().endswith("_cv"):
                    continue
                if not under_lock(n):
                    self._emit(n, "GL006",
                               "store to shared attribute %s outside a "
                               "`with <lock>` block in serving code "
                               "(document intentionally lock-free "
                               "writes with a suppression)" % name)

    # -- GL007 ----------------------------------------------------------
    def check_global_config(self) -> None:
        if self.rel in ENTRY_MODULES:
            return
        for n in ast.walk(self.tree):
            if not isinstance(n, ast.Call):
                continue
            if _dotted(n.func) != "jax.config.update":
                continue
            if n.args and isinstance(n.args[0], ast.Constant) \
                    and n.args[0].value in GLOBAL_JAX_KNOBS:
                self._emit(n, "GL007",
                           "jax.config.update(%r) outside the CLI "
                           "entry points: a library import must not "
                           "reconfigure its host process"
                           % n.args[0].value)

    # -- GL008 ----------------------------------------------------------
    def check_stdio(self) -> None:
        # the analysis package is developer tooling: its own report
        # printing is not part of the training-log surface
        if self.rel in STDIO_EXEMPT or self.rel.startswith("analysis/"):
            return
        for n in ast.walk(self.tree):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                    and n.func.id == "print":
                self._emit(n, "GL008",
                           "print() bypasses utils/log — training-log "
                           "parity diffs depend on the logger "
                           "formatting every line")
            elif isinstance(n, ast.Attribute) \
                    and _dotted(n) in ("sys.stdout", "sys.stderr"):
                self._emit(n, "GL008",
                           "%s used directly; route output through "
                           "utils/log" % _dotted(n))

    # -- driver ----------------------------------------------------------
    def run(self) -> List[Finding]:
        self.check_traced()
        self.check_jax_free()
        self.check_parity()
        self.check_serving_locks()
        self.check_global_config()
        self.check_stdio()
        return self._apply_suppressions()

    def _next_code_line(self, after: int) -> Optional[int]:
        """1-based number of the first non-blank, non-comment line
        strictly after `after` (justifications may span several comment
        lines; the suppression binds to the code they precede)."""
        for i in range(after, len(self.lines)):
            stripped = self.lines[i].strip()
            if stripped and not stripped.startswith("#"):
                return i + 1
        return None

    def _decorator_def_lines(self) -> Dict[int, int]:
        """Line of each decorator -> line of the def/class it adorns: a
        suppression comment written ABOVE a decorator must still bind
        to the def (findings anchor on the def line, not the decorator
        line)."""
        out: Dict[int, int] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)) and node.decorator_list:
                for dec in node.decorator_list:
                    out[dec.lineno] = node.lineno
        return out

    def _apply_suppressions(self) -> List[Finding]:
        dec_to_def = self._decorator_def_lines()
        by_line: Dict[int, List[Suppression]] = {}
        for s in self.suppressions:
            by_line.setdefault(s.line, []).append(s)
            if s.own_line:
                target = self._next_code_line(s.line)
                if target is not None:
                    by_line.setdefault(target, []).append(s)
                    # comment above a decorated def: the next code line
                    # is the decorator, but the finding sits on the def
                    def_line = dec_to_def.get(target)
                    if def_line is not None:
                        by_line.setdefault(def_line, []).append(s)
        kept: List[Finding] = []
        for f in self.findings:
            hit = None
            for s in by_line.get(f.line, []):
                if f.rule in s.rules and f.rule not in UNSUPPRESSABLE:
                    hit = s
                    break
            if hit is None:
                kept.append(f)
            else:
                hit.used_rules.add(f.rule)
        for s in self.suppressions:
            unknown = [r for r in s.rules if r not in RULES]
            for r in unknown:
                kept.append(Finding(self.display, s.line, "GL009",
                                    "suppression names unknown rule %r"
                                    % r))
            if len(s.justification) < MIN_JUSTIFICATION_CHARS:
                kept.append(Finding(
                    self.display, s.line, "GL009",
                    "suppression of %s carries no real justification "
                    "(want `-- <why this invariant is safe to waive "
                    "here>`, >= %d chars)"
                    % (",".join(s.rules), MIN_JUSTIFICATION_CHARS)))
            for r in s.rules:
                if r in RULES and r not in s.used_rules:
                    kept.append(Finding(
                        self.display, s.line, "GL010",
                        "suppression of %s did not match any finding "
                        "on its line — stale, remove it" % r))
        kept.sort(key=lambda f: (f.path, f.line, f.rule))
        return kept


# populated per run: every module path in the package (for GL002's
# transitive resolution) and the subset declaring __jax_free__ = True
_ALL_MODULES: Set[str] = set()
_JAX_FREE: Set[str] = set()

# memoized package index per root: lint_source() is called ~100 times
# per test run and must not re-read + re-parse the whole package each
# time.  run_graftlint() always refreshes (it reads the files anyway).
_INDEX_CACHE: Dict[str, Tuple[Set[str], Set[str]]] = {}


def _package_index(root: str) -> Tuple[Set[str], Set[str]]:
    got = _INDEX_CACHE.get(root)
    if got is None:
        mods = {os.path.relpath(p, root).replace(os.sep, "/")
                for p in iter_package_files(root)}
        got = (mods, _discover_jax_free(root))
        _INDEX_CACHE[root] = got
    return got


def _discover_jax_free(root: str) -> Set[str]:
    """Package-relative paths of every module declaring
    `__jax_free__ = True` under `root`."""
    out: Set[str] = set()
    for path in iter_package_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        if _source_declares_jax_free(src):
            out.add(rel)
    return out


def package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def iter_package_files(root: str) -> List[str]:
    out = []
    for dirpath, _dirnames, filenames in os.walk(root):
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return sorted(out)


def run_graftlint(paths: Optional[Sequence[str]] = None,
                  root: Optional[str] = None) -> List[Finding]:
    """Lint package files; returns surviving findings (already
    suppression-filtered).  `paths` defaults to every .py in the
    package rooted at `root` (default: the installed lightgbm_tpu)."""
    root = root or package_root()
    files = list(paths) if paths else iter_package_files(root)
    global _ALL_MODULES, _JAX_FREE
    _ALL_MODULES = {
        os.path.relpath(p, root).replace(os.sep, "/")
        for p in iter_package_files(root)}
    _JAX_FREE = _discover_jax_free(root)
    _INDEX_CACHE[root] = (_ALL_MODULES, _JAX_FREE)  # refresh the memo
    findings: List[Finding] = []
    for path in files:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
        except OSError as ex:
            findings.append(Finding(path, 1, "GL009",
                                    "unreadable file: %s" % ex))
            continue
        display = os.path.relpath(path, os.getcwd()) \
            if os.path.isabs(path) else path
        try:
            lint = ModuleLint(rel, src, display)
        except SyntaxError as ex:
            findings.append(Finding(display, ex.lineno or 1, "GL009",
                                    "syntax error: %s" % ex.msg))
            continue
        findings.extend(lint.run())
    return findings


def lint_source(source: str, relpath: str) -> List[Finding]:
    """Lint one in-memory module as if it lived at `relpath` inside the
    package (test helper)."""
    global _ALL_MODULES, _JAX_FREE
    saved, saved_free = _ALL_MODULES, _JAX_FREE
    try:
        if not _ALL_MODULES:
            _ALL_MODULES, _JAX_FREE = _package_index(package_root())
        return ModuleLint(relpath, source, relpath).run()
    finally:
        _ALL_MODULES, _JAX_FREE = saved, saved_free
