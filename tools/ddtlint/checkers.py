"""The ddtlint rules — one small, individually-testable visitor per hazard.

Every checker is deliberately biased toward *no false negatives on the
fixture shapes, no false positives on idiomatic repo code*: anything it
cannot resolve statically it skips, and the pytest gate's ratchet baseline
(tools/ddtlint/baseline.json) absorbs the residue.  docs/ANALYSIS.md
documents each rule's rationale, scope, and escape hatches.
"""

from __future__ import annotations

import ast
import re

from tools.ddtlint import (callgraph, configflow, shardspec,
                           telemetrycontract, threadmodel)
from tools.ddtlint.base import Checker, CheckContext  # noqa: F401 — the
# base moved to tools/ddtlint/base.py so the flow-aware pass modules can
# subclass it without an import cycle; re-exported here for callers.
from tools.ddtlint.findings import Finding

# Attribute-chain roots that produce traced arrays when called.
_TRACED_ROOTS = ("jnp.", "jax.", "lax.")
# jax/jnp callables that return HOST values (python bools/strings/ints),
# not traced arrays — assignments from these must not taint.
_HOST_FUNCS = {
    "default_backend", "devices", "local_devices", "device_count",
    "local_device_count", "process_index", "process_count",
    "issubdtype", "result_type", "promote_types", "dtype", "shape",
    "ndim", "iinfo", "finfo", "axis_size", "Precision",
}


def _is_traced_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = callgraph.dotted(node.func)
    if d is None or not (d + ".").startswith(_TRACED_ROOTS):
        return False
    return d.split(".")[-1] not in _HOST_FUNCS \
        and not callgraph._resolves_to_jit(node.func)


# --------------------------------------------------------------------- #
# 1. traced-branch
# --------------------------------------------------------------------- #
class TracedBranchChecker(Checker):
    """Python `if`/`while` (and ternaries) on traced values inside functions
    reachable from a jit/pjit root — a TracerBoolConversionError on device,
    invisible to eager CPU tests.  Taint: locals assigned from jnp./jax.
    calls, propagated through expressions; parameters are NOT tainted
    (static-argument branches are the dominant legitimate pattern in ops/).
    `x is None` / isinstance() tests are static Python and exempt."""

    rule = "traced-branch"
    path_scope = (r"^ddt_tpu/ops/", r"^ddt_tpu/backends/")

    def run(self) -> list[Finding]:
        for qual in sorted(self.ctx.reachable):
            fn = self._find_func(qual)
            if fn is not None:
                self._check_fn(qual, fn)
        return self.findings

    def _find_func(self, qual: str):
        parts = qual.split(".")
        node: ast.AST = self.ctx.tree
        for name in parts:
            found = None
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)) and child.name == name:
                    found = child
                    break
            if found is None:
                return None
            node = found
        return node if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)) else None

    @classmethod
    def _walk_own(cls, fn: ast.AST):
        """Descendants of `fn` excluding nested function bodies — nested
        defs are reachable in their own right (callgraph closure), so
        checking them here would double-report."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                stack.extend(ast.iter_child_nodes(node))

    def _check_fn(self, qual: str, fn: ast.AST) -> None:
        tainted = self._taint(fn)
        for node in self._walk_own(fn):
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                test = node.test
                if self._static_test(test):
                    continue
                if self._traced_expr(test, tainted):
                    kind = {ast.If: "if", ast.While: "while",
                            ast.IfExp: "conditional expression"}[type(node)]
                    self.report(node, (
                        f"Python {kind} on a traced value in jit-reachable "
                        f"'{qual}' — use jnp.where / lax.cond / "
                        "lax.while_loop (traces as data, not control flow)"))

    @staticmethod
    def _static_test(test: ast.AST) -> bool:
        """Tests that stay in Python even on traced operands."""
        while isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            test = test.operand
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return True
        if isinstance(test, ast.Call):
            d = callgraph.dotted(test.func)
            if d in ("isinstance", "hasattr", "callable", "len"):
                return True
            # host-returning jax/jnp predicates stay python bools even on
            # traced operands (jnp.issubdtype(x.dtype, ...), etc.)
            if d is not None and d.split(".")[-1] in _HOST_FUNCS:
                return True
        return False

    @classmethod
    def _taint(cls, fn: ast.AST) -> set[str]:
        tainted: set[str] = set()

        def expr_traced(e: ast.AST) -> bool:
            for n in ast.walk(e):
                if _is_traced_call(n):
                    return True
                if isinstance(n, ast.Name) and n.id in tainted:
                    return True
            return False

        def add_target(t: ast.AST):
            if isinstance(t, ast.Name):
                tainted.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    add_target(e)

        # _walk_own, not ast.walk: nested defs are separate scopes checked
        # in their own right — a jnp-assigned name INSIDE a nested def must
        # not taint the same name in the enclosing function.
        for _ in range(8):                    # fixpoint; converges fast
            n0 = len(tainted)
            for node in cls._walk_own(fn):
                if isinstance(node, ast.Assign) and expr_traced(node.value):
                    for t in node.targets:
                        add_target(t)
                elif isinstance(node, ast.AugAssign) \
                        and expr_traced(node.value):
                    add_target(node.target)
                elif isinstance(node, ast.AnnAssign) \
                        and node.value is not None \
                        and expr_traced(node.value):
                    add_target(node.target)
            if len(tainted) == n0:
                break
        return tainted

    def _traced_expr(self, e: ast.AST, tainted: set[str]) -> bool:
        for n in ast.walk(e):
            if _is_traced_call(n):
                return True
            if isinstance(n, ast.Name) and n.id in tainted:
                return True
        return False


# --------------------------------------------------------------------- #
# 2. host-sync
# --------------------------------------------------------------------- #
class HostSyncChecker(Checker):
    """`.item()`, `float()`, `int()`, `np.asarray()` on arrays inside the
    grow/stream/scoring loops: each one is a blocking device->host fetch
    that serialises the dispatch pipeline.  Scoped to
    the hot-loop files; loop bodies (for/while/comprehensions) only."""

    rule = "host-sync"
    path_scope = (r"^ddt_tpu/ops/grow\.py$", r"^ddt_tpu/ops/stream\.py$",
                  r"^ddt_tpu/backends/tpu\.py$")
    _LOOPS = (ast.For, ast.AsyncFor, ast.While, ast.ListComp, ast.SetComp,
              ast.DictComp, ast.GeneratorExp)

    def run(self) -> list[Finding]:
        for loop in ast.walk(self.ctx.tree):
            if isinstance(loop, self._LOOPS):
                self._check_loop(loop)
        # dedupe: nested loops visit the same node twice
        seen, out = set(), []
        for f in self.findings:
            k = (f.line, f.col, f.message)
            if k not in seen:
                seen.add(k)
                out.append(f)
        self.findings = out
        return self.findings

    def _check_loop(self, loop: ast.AST) -> None:
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            d = callgraph.dotted(node.func)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                self.report(node, "`.item()` in a loop body forces a "
                                  "blocking device->host sync per iteration")
            elif d in ("float", "int") and len(node.args) == 1 \
                    and not isinstance(node.args[0], ast.Constant):
                self.report(node, (
                    f"`{d}()` on an array in a loop body blocks on the "
                    "device — hoist the sync out of the loop or keep the "
                    "value on device"))
            elif d in ("np.asarray", "np.array", "numpy.asarray",
                       "numpy.array"):
                self.report(node, (
                    f"`{d}()` in a loop body copies device memory to host "
                    "per iteration — batch the fetch outside the loop"))


# --------------------------------------------------------------------- #
# 3. dtype-drift
# --------------------------------------------------------------------- #
class DtypeDriftChecker(Checker):
    """Array constructors without an explicit dtype in ops/: the default
    (f32 vs x64-mode f64, plus weak-type promotion) differs between the
    CPU and TPU backends and between jax configs, so accumulator dtypes
    must be spelled out.  Also flags bare float literals flowing into
    histogram builders/accumulators, where a weakly-typed Python float
    silently upcasts a bf16/f32 accumulation."""

    rule = "dtype-drift"
    path_scope = (r"^ddt_tpu/ops/",)
    # ctor -> index of the positional dtype parameter
    _CTORS = {"jnp.zeros": 1, "jnp.ones": 1, "jnp.array": 1, "jnp.empty": 1}
    _HIST_RE = re.compile(r"(hist|acc)", re.IGNORECASE)

    def visit_Call(self, node: ast.Call):
        d = callgraph.dotted(node.func)
        if d in self._CTORS:
            pos = self._CTORS[d]
            has_dtype = len(node.args) > pos or any(
                k.arg == "dtype" for k in node.keywords)
            if not has_dtype:
                self.report(node, (
                    f"`{d}(...)` without an explicit dtype — the default "
                    "drifts between backends/x64 mode; pass dtype= "
                    "(positionally or by keyword)"))
        if d is not None and "histogram" in d.split(".")[-1].lower():
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                                float):
                    self.report(arg, (
                        "bare float literal passed into a histogram "
                        "builder — wrap in jnp.float32(...) to pin the "
                        "accumulator dtype"))
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        if isinstance(node.target, ast.Name) \
                and self._HIST_RE.search(node.target.id) \
                and self._bare_float(node.value):
            self.report(node, (
                f"bare float literal accumulated into `{node.target.id}` — "
                "weak-type promotion can upcast the histogram dtype; wrap "
                "in jnp.float32(...)"))
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp):
        pairs = ((node.left, node.right), (node.right, node.left))
        for name_side, lit_side in pairs:
            if isinstance(name_side, ast.Name) \
                    and self._HIST_RE.search(name_side.id) \
                    and isinstance(lit_side, ast.Constant) \
                    and isinstance(lit_side.value, float):
                self.report(node, (
                    f"bare float literal combined with `{name_side.id}` — "
                    "weak-type promotion can upcast the histogram dtype; "
                    "wrap in jnp.float32(...)"))
                break
        self.generic_visit(node)

    @staticmethod
    def _bare_float(e: ast.AST) -> bool:
        return isinstance(e, ast.Constant) and isinstance(e.value, float)


# --------------------------------------------------------------------- #
# 4. collective-consistency
# --------------------------------------------------------------------- #
class CollectiveAxisChecker(Checker):
    """String axis names in collectives must exist on a mesh defined in
    parallel/mesh.py — a typo'd axis traces fine on one device and dies
    (or worse, silently no-ops the reduction) under shard_map on the pod.
    Variable axis arguments are skipped (plumbed from the mesh at runtime,
    which is exactly the safe pattern)."""

    rule = "collective-consistency"
    path_scope = (r"^ddt_tpu/",)
    # collective -> positional index of the axis-name argument
    _AXIS_POS = {
        "psum": 1, "psum_scatter": 1, "pmin": 1, "pmax": 1, "pmean": 1,
        "all_gather": 1, "all_to_all": 1, "ppermute": 1,
        "axis_index": 0, "axis_size": 0,
    }

    def visit_Call(self, node: ast.Call):
        d = callgraph.dotted(node.func)
        last = d.split(".")[-1] if d else None
        if last in self._AXIS_POS and d != last:   # require lax./jax.lax.
            axis = None
            for k in node.keywords:
                if k.arg in ("axis_name", "axis_names"):
                    axis = k.value
            pos = self._AXIS_POS[last]
            if axis is None and len(node.args) > pos:
                axis = node.args[pos]
            for name in self._literal_axes(axis):
                if name not in self.ctx.mesh_axes:
                    known = ", ".join(sorted(self.ctx.mesh_axes)) or "(none)"
                    self.report(node, (
                        f"`{last}` over axis {name!r} which no mesh in "
                        f"parallel/mesh.py defines (known axes: {known}) — "
                        "mismatched collective axis names deadlock or "
                        "mis-reduce under shard_map"))
        self.generic_visit(node)

    @staticmethod
    def _literal_axes(axis: ast.AST | None) -> list[str]:
        if axis is None:
            return []
        if isinstance(axis, ast.Constant) and isinstance(axis.value, str):
            return [axis.value]
        if isinstance(axis, (ast.Tuple, ast.List)):
            return [e.value for e in axis.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)]
        return []


# --------------------------------------------------------------------- #
# 5. broad-except
# --------------------------------------------------------------------- #
class BroadExceptChecker(Checker):
    """`except Exception` / bare `except` swallow real faults (the
    conftest thread-pin finding: a ctypes TypeError became nondeterministic
    bit-identity flakes).  Handlers that re-raise are exempt — translating
    an exception type is the legitimate use of a broad catch."""

    rule = "broad-except"
    path_scope = None                         # everywhere scanned

    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        broad = False
        if node.type is None:
            broad = True
        else:
            names = []
            if isinstance(node.type, ast.Tuple):
                names = [callgraph.dotted(e) for e in node.type.elts]
            else:
                names = [callgraph.dotted(node.type)]
            broad = any(n in ("Exception", "BaseException") for n in names)
        if broad and not any(isinstance(n, ast.Raise)
                             for n in ast.walk(node)):
            what = "bare `except:`" if node.type is None \
                else "`except Exception`"
            self.report(node, (
                f"{what} without re-raise swallows unexpected faults — "
                "narrow to the exception types the fallback is designed "
                "for (e.g. `except (ImportError, OSError)`)"))
        self.generic_visit(node)


# --------------------------------------------------------------------- #
# 6. no-print
# --------------------------------------------------------------------- #
class NoPrintChecker(Checker):
    """Bare `print(...)` in ddt_tpu/ LIBRARY code: invisible to logging
    config, unparseable by log shippers, and — since the telemetry PR —
    redundant with the structured event stream every trainer can emit.
    The CLI (ddt_tpu/cli.py) is exempt (stdout JSON lines ARE its
    interface), as are tools/ and tests/ (outside the scanned scope /
    path_scope). Only the BUILTIN name counts: methods named print and
    callables passed in as parameters are fine."""

    rule = "no-print"
    # Negative lookahead: everything under ddt_tpu/ except the CLI.
    path_scope = (r"^ddt_tpu/(?!cli\.py$)",)

    def visit_Call(self, node: ast.Call):
        if isinstance(node.func, ast.Name) and node.func.id == "print":
            self.report(node, (
                "bare `print(...)` in ddt_tpu library code — emit a "
                "telemetry event (ddt_tpu.telemetry.RunLog.emit) or use "
                "the module logger; stdout belongs to the CLI"))
        self.generic_visit(node)


# --------------------------------------------------------------------- #
# 7. pallas-interpret
# --------------------------------------------------------------------- #
class PallasInterpretChecker(Checker):
    """`pl.pallas_call` sites must carry a LIVE `interpret=` operand — a
    variable the dispatcher resolves (the hist_pallas/predict_pallas
    idiom: `interpret=None` auto-selects the Pallas interpreter off-TPU).
    A call site with no interpret kwarg, or a hard `interpret=False`,
    has no interpret-mode fallback path: the kernel cannot run on the
    CPU tier-1 suite, so its logic ships untested and every later edit
    is verified only on a real chip.  Pallas kernels are jit-reachability
    roots (callgraph.TRACING_COMBINATORS includes pallas_call, bare or
    partial()-wrapped), so the traced-branch rule already covers the
    kernel BODY; this rule covers its DISPATCH."""

    rule = "pallas-interpret"
    path_scope = (r"^ddt_tpu/",)

    def visit_Call(self, node: ast.Call):
        d = callgraph.dotted(node.func)
        if d is not None and d.split(".")[-1] == "pallas_call":
            interp = None
            has_kwarg = False
            for k in node.keywords:
                if k.arg == "interpret":
                    has_kwarg = True
                    interp = k.value
            if not has_kwarg:
                self.report(node, (
                    "`pallas_call` without an `interpret=` operand — the "
                    "kernel has no interpret-mode fallback path and "
                    "cannot run on the CPU test suite; thread an "
                    "`interpret` parameter through the dispatcher "
                    "(None = auto-select off-TPU, the hist_pallas "
                    "pattern)"))
            elif isinstance(interp, ast.Constant) \
                    and interp.value in (False, None):
                self.report(node, (
                    f"`pallas_call` hard-codes interpret="
                    f"{interp.value!r} — the interpreter fallback is "
                    "unreachable; pass a dispatcher-resolved variable "
                    "(None = auto-select off-TPU, the hist_pallas "
                    "pattern)"))
        self.generic_visit(node)


# --------------------------------------------------------------------- #
# 7b. pallas-vmem-guard
# --------------------------------------------------------------------- #
class PallasVmemGuardChecker(Checker):
    """Every `pl.pallas_call` dispatch site must sit behind a VMEM-fits
    predicate — a call whose name matches `*fits*` / `*chunks_for*`
    (the hist_pallas.pallas_fits / feature_chunks_for /
    predict_pallas.predict_pallas_fits idiom) — in the dispatching
    function itself or in a module-local (transitive) caller. A Pallas
    kernel pins its whole working set in VMEM: an unguarded dispatch at
    a shape past the ~16 MB/core budget dies as a Mosaic allocation
    failure (or a silent multi-minute pathological compile) ON THE CHIP
    ONLY — the CPU interpret-mode tests never see it, so the guard is
    the one thing standing between a new config knob and a fleet crash.
    Dispatch units are module-level functions, class METHODS, and
    module-scope code (no pallas_call site can hide by where it sits);
    cross-module dispatchers don't count: the module that owns the
    kernel must own (or call) its own budget predicate, so the guard and
    the kernel's VMEM layout can never drift apart in separate files."""

    rule = "pallas-vmem-guard"
    path_scope = (r"^ddt_tpu/",)
    _GUARD_RE = re.compile(r"fits|chunks_for")

    def _units(self):
        """(qualname, node) dispatch units: module-level functions,
        CLASS METHODS (qualified `Class.method` so same-named methods in
        different classes keep distinct guard status), and a `<module>`
        pseudo-unit for module-scope statements — no pallas_call site
        can hide from the scan by where it sits. Nested defs stay part
        of their enclosing unit (they dispatch under its entry point).
        Call EDGES still resolve on the bare last name (`self.m()` and
        `obj.m()` are indistinguishable statically), conservatively
        linking every same-named unit."""
        defs = (ast.FunctionDef, ast.AsyncFunctionDef)
        for node in ast.iter_child_nodes(self.ctx.tree):
            if isinstance(node, defs):
                yield node.name, node
            elif isinstance(node, ast.ClassDef):
                for m in ast.iter_child_nodes(node):
                    if isinstance(m, defs):
                        yield f"{node.name}.{m.name}", m
        # Module scope: everything outside the units above.
        mod = ast.Module(
            body=[n for n in self.ctx.tree.body
                  if not isinstance(n, defs + (ast.ClassDef,))],
            type_ignores=[])
        yield "<module>", mod

    def run(self) -> list[Finding]:
        calls: dict[str, set[str]] = {}       # qual -> called last-names
        guarded: set[str] = set()             # quals with a fits call
        dispatches: dict[str, list[ast.AST]] = {}
        by_bare: dict[str, list[str]] = {}    # bare name -> quals
        for qual, fn in self._units():
            by_bare.setdefault(qual.split(".")[-1], []).append(qual)
            called: set[str] = set()
            for n in ast.walk(fn):               # incl. nested defs: they
                if not isinstance(n, ast.Call):  # dispatch under the
                    continue                     # enclosing entry point
                d = callgraph.dotted(n.func)
                if d is None:
                    continue
                last = d.split(".")[-1]
                called.add(last)
                if last == "pallas_call":
                    dispatches.setdefault(qual, []).append(n)
                if self._GUARD_RE.search(last):
                    guarded.add(qual)
            calls[qual] = called

        # Reverse reachability: the dispatching unit plus every
        # module-local transitive caller (a called bare name links every
        # unit carrying it).
        callers: dict[str, set[str]] = {q: set() for q in calls}
        for src, called in calls.items():
            for c in called:
                for target in by_bare.get(c, ()):
                    callers[target].add(src)

        for qual, sites in dispatches.items():
            seen, stack = {qual}, [qual]
            ok = False
            while stack and not ok:
                cur = stack.pop()
                if cur in guarded:
                    ok = True
                    break
                for up in callers.get(cur, ()):
                    if up not in seen:
                        seen.add(up)
                        stack.append(up)
            if ok:
                continue
            for site in sites:
                self.report(site, (
                    f"`pallas_call` in '{qual}' has no VMEM-fits guard on "
                    "its module-local dispatch chain — gate the dispatch "
                    "behind a budget predicate (the hist_pallas."
                    "pallas_fits / feature_chunks_for pattern) so "
                    "over-budget shapes fail at the cause instead of as "
                    "an on-chip Mosaic VMEM allocation failure"))
        return self.findings


# --------------------------------------------------------------------- #
# 8. named-scope
# --------------------------------------------------------------------- #
class NamedScopeChecker(Checker):
    """Every jit-reachable op ENTRY POINT in ddt_tpu/ops/ — a public
    top-level function that lowers device work (contains jnp./jax./lax.
    array calls) — must open a `ddt:`-prefixed scope
    (telemetry.annotations.traced_scope, or jax.named_scope with a
    literal "ddt:..." name) somewhere in its body, so XLA op metadata —
    and therefore Perfetto/trace-export timelines — stays attributable
    to the pipeline stage that emitted it (docs/OBSERVABILITY.md
    "Phase timing and Perfetto alignment"). Host-only helpers (shape
    math, impl resolvers) contain no traced calls and are exempt;
    private helpers and nested defs trace under their caller's scope."""

    rule = "named-scope"
    path_scope = (r"^ddt_tpu/ops/",)

    def run(self) -> list[Finding]:
        for node in ast.iter_child_nodes(self.ctx.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            if node.name.startswith("_"):
                continue
            if node.name not in self.ctx.reachable:
                continue                      # never traced: no HLO to name
            if not self._does_device_work(node):
                continue                      # host-only helper
            if self._opens_ddt_scope(node):
                continue
            self.report(node, (
                f"jit-reachable op entry point '{node.name}' opens no "
                "`ddt:` named scope — wrap its device work in "
                "telemetry.annotations.traced_scope(...) so traces stay "
                "attributable (docs/OBSERVABILITY.md)"))
        return self.findings

    @staticmethod
    def _does_device_work(fn: ast.AST) -> bool:
        return any(_is_traced_call(n) for n in ast.walk(fn))

    @staticmethod
    def _opens_ddt_scope(fn: ast.AST) -> bool:
        for n in ast.walk(fn):
            if not isinstance(n, ast.Call):
                continue
            d = callgraph.dotted(n.func)
            if d is None:
                continue
            last = d.split(".")[-1]
            # Both telemetry.annotations spellings add the ddt: prefix
            # themselves: traced_scope (with-block) / op_scope (decorator).
            if last in ("traced_scope", "op_scope"):
                return True
            if last == "named_scope" and n.args \
                    and isinstance(n.args[0], ast.Constant) \
                    and isinstance(n.args[0].value, str) \
                    and n.args[0].value.startswith("ddt:"):
                return True
        return False


# --------------------------------------------------------------------- #
# 9. atomic-artifact-write
# --------------------------------------------------------------------- #
class AtomicArtifactWriteChecker(Checker):
    """Persistent artifacts (checkpoints, model files, chunk caches)
    must be written tmp-then-`os.replace` — a direct
    `np.savez(final, ...)` / `open(final, "w")` killed mid-write leaves
    a TORN artifact at the canonical name, which a later resume/load
    then chokes on (the checkpoint-hardening bug class,
    docs/ROBUSTNESS.md). Scoped to the artifact-owning modules
    (utils/checkpoint.py, api.py, models/, data/chunks.py, and — since
    the model registry (ISSUE 9) — ddt_tpu/registry/, whose manifests
    and name indexes are exactly the small-JSON-beside-big-npz pair the
    checkpoint hardening story is about); a write is compliant when its
    path expression is tmp-like — a name/attribute/literal containing
    "tmp", or anything tempfile-derived — because the
    tmp-name-then-replace dance is exactly the pattern the rule exists
    to enforce. Read modes and append modes are exempt (appends are
    logs, not artifact overwrites; the run log's crash story is
    line-granularity by design). ddt_tpu/export/ stays OUT of scope by
    design: its writers only ever target a registry STAGING directory,
    which publishes wholesale via one atomic os.rename
    (registry/store.py) — the directory is the tmp sibling."""

    rule = "atomic-artifact-write"
    path_scope = (r"^ddt_tpu/utils/checkpoint\.py$", r"^ddt_tpu/api\.py$",
                  r"^ddt_tpu/models/", r"^ddt_tpu/data/chunks\.py$",
                  r"^ddt_tpu/registry/")
    _WRITERS = {"np.save", "np.savez", "np.savez_compressed",
                "numpy.save", "numpy.savez", "numpy.savez_compressed"}

    def visit_Call(self, node: ast.Call):
        d = callgraph.dotted(node.func)
        if d in self._WRITERS and node.args \
                and not self._tmp_like(node.args[0]):
            self.report(node, (
                f"`{d}(...)` writes a persistent artifact directly to its "
                "final path — a kill mid-write leaves a torn file there; "
                "write to a tmp-suffixed sibling and `os.replace` it "
                "(docs/ROBUSTNESS.md atomic-artifact-write)"))
        elif d == "open" and node.args:
            mode = self._mode(node)
            if mode is not None and ("w" in mode or "x" in mode) \
                    and not self._tmp_like(node.args[0]):
                self.report(node, (
                    f"`open(..., {mode!r})` truncates a persistent "
                    "artifact in place — a kill mid-write leaves a torn "
                    "file at the final path; write a tmp-suffixed sibling "
                    "and `os.replace` it (docs/ROBUSTNESS.md "
                    "atomic-artifact-write)"))
        self.generic_visit(node)

    @staticmethod
    def _mode(node: ast.Call) -> str | None:
        if len(node.args) > 1 and isinstance(node.args[1], ast.Constant) \
                and isinstance(node.args[1].value, str):
            return node.args[1].value
        for k in node.keywords:
            if k.arg == "mode" and isinstance(k.value, ast.Constant) \
                    and isinstance(k.value.value, str):
                return k.value.value
        return None

    @staticmethod
    def _tmp_like(e: ast.AST) -> bool:
        for n in ast.walk(e):
            if isinstance(n, ast.Name) and "tmp" in n.id.lower():
                return True
            if isinstance(n, ast.Attribute) and "tmp" in n.attr.lower():
                return True
            if isinstance(n, ast.Constant) and isinstance(n.value, str) \
                    and "tmp" in n.value.lower():
                return True
            if isinstance(n, ast.Call):
                d = callgraph.dotted(n.func)
                if d is not None and (
                        d.startswith("tempfile.")
                        or "temp" in d.split(".")[-1].lower()):
                    return True
        return False


# --------------------------------------------------------------------- #
# 10. raw-phase-timing
# --------------------------------------------------------------------- #
class RawPhaseTimingChecker(Checker):
    """Raw host clocks (`time.time()` / `time.perf_counter()` /
    `time.monotonic()`, and their _ns twins) in the device-op layer
    (ddt_tpu/ops/, ddt_tpu/backends/): a host timestamp around device
    work measures DISPATCH, not the device — XLA enqueues asynchronously,
    so the number silently reports queue depth and looks plausible in a
    log.  Phase timing belongs at the trainer layer through
    PhaseTimer/phase_ctx (utils/profiling.py + telemetry/annotations.py,
    which pair the wallclock with the required sync discipline and emit
    it into the run log); device-side attribution belongs to the named
    `ddt:` scopes + the cost observatory (telemetry/costmodel.py), not a
    clock.  The trainer loops (driver/streaming — PhaseTimer's
    consumers), the timing subsystem itself, the shard-readiness probe
    (parallel/mesh.py), the benchmark, cli, and tests are all outside
    the scope: their clocks ARE the instrument.  time.sleep and the time
    module's non-clock helpers are not flagged."""

    rule = "raw-phase-timing"
    path_scope = (r"^ddt_tpu/ops/", r"^ddt_tpu/backends/")
    _CLOCKS = {"time.time", "time.perf_counter", "time.monotonic",
               "time.perf_counter_ns", "time.monotonic_ns",
               "time.process_time", "time.process_time_ns"}

    def visit_Call(self, node: ast.Call):
        d = callgraph.dotted(node.func)
        if d in self._CLOCKS:
            self.report(node, (
                f"`{d}()` in the device-op layer times DISPATCH, not the "
                "device (XLA enqueues asynchronously) — time phases at "
                "the trainer layer via PhaseTimer/phase_ctx "
                "(telemetry/annotations.py), or attribute device work "
                "with `ddt:` scopes + the cost observatory "
                "(docs/OBSERVABILITY.md)"))
        self.generic_visit(node)


# --------------------------------------------------------------------- #
# 11. serve-blocking-io
# --------------------------------------------------------------------- #
class ServeBlockingIOChecker(Checker):
    """Blocking host I/O in the serving tier's HOT-LOOP modules
    (ddt_tpu/serve/batcher.py + engine.py): the admission batcher's
    dispatcher thread is shared by EVERY in-flight request — one
    `time.sleep` poll or synchronous file read there adds its wall time
    to the whole queue's tail latency, invisibly (the p999 the SLO
    counters exist to expose). Since the express lane (ISSUE 12) the
    stakes are doubled: the SAME dispatch path (`ServeEngine._dispatch`
    and everything it reaches) also runs synchronously on HTTP handler
    threads for empty-queue single-row requests, so a blocking call
    there is both the whole queue's tail tax AND the express path's
    whole latency budget — the lane exists to score in ~dispatch time,
    and one file read erases it. Flagged: `time.sleep` (park on a
    Condition/Event with a timeout instead — the batcher's admission
    window does exactly that), `open(...)` in any mode, `np.load` /
    `json.load`, and Path `.read_text`/`.read_bytes` (model files load
    in the cli/http layer and arrive as ready ModelBundles —
    docs/SERVING.md "Hot swap"). The transport layer (serve/http.py)
    and everything outside ddt_tpu/serve/ are out of scope: their
    blocking is the caller's thread, not the dispatch path's."""

    rule = "serve-blocking-io"
    path_scope = (r"^ddt_tpu/serve/batcher\.py$",
                  r"^ddt_tpu/serve/engine\.py$")
    _BLOCKING_CALLS = {"time.sleep", "open", "np.load", "numpy.load",
                       "json.load"}
    _READ_ATTRS = {"read_text", "read_bytes"}

    def visit_Call(self, node: ast.Call):
        d = callgraph.dotted(node.func)
        if d in self._BLOCKING_CALLS:
            self.report(node, (
                f"`{d}(...)` in a serving hot-loop module blocks the "
                "shared dispatch path — it taxes every in-flight "
                "request's tail latency on the dispatcher thread AND "
                "is the express lane's whole latency budget on the "
                "handler thread — park on a Condition/Event timeout, "
                "or move the I/O to the cli/http layer "
                "(docs/SERVING.md; ddtlint serve-blocking-io)"))
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in self._READ_ATTRS:
            self.report(node, (
                f"`.{node.func.attr}()` in a serving hot-loop module is "
                "a synchronous file read on the shared dispatcher "
                "thread — load artifacts in the cli/http layer and hand "
                "the engine ready objects (docs/SERVING.md; ddtlint "
                "serve-blocking-io)"))
        self.generic_visit(node)


# --------------------------------------------------------------------- #
# 12. one-home-collective
# --------------------------------------------------------------------- #
class OneHomeCollectiveChecker(Checker):
    """Raw `jax.lax` collectives outside parallel/comms.py: every
    cross-device byte the trainer moves must funnel through the one-home
    comms module (psum/pmax/pmin/all_gather/reduce_scatter wrappers with
    version-portable fallbacks, compression, `ddt:comms:*` scopes) — a
    raw psum elsewhere silently bypasses split_comms/hist_comms_dtype
    AND desynchronizes the `hist_allreduce_bytes` payload model from the
    wire it claims to estimate. comms.py itself is the sanctioned home;
    `axis_index`/`axis_size` are topology reads, not traffic, and stay
    legal everywhere (collective-consistency still checks their axis
    names)."""

    rule = "one-home-collective"
    path_scope = (r"^ddt_tpu/(?!parallel/comms\.py$)",)
    _COLLECTIVES = {
        "psum", "psum_scatter", "pmin", "pmax", "pmean",
        "all_gather", "all_to_all", "ppermute", "pshuffle",
    }

    def visit_Call(self, node: ast.Call):
        d = callgraph.dotted(node.func)
        last = d.split(".")[-1] if d else None
        # Require the lax./jax.lax. spelling (like collective-consistency):
        # comms.psum(...) and locally-defined helpers named psum are the
        # sanctioned indirections, not raw collectives.
        if last in self._COLLECTIVES and d != last \
                and d.split(".")[-2] in ("lax",):
            self.report(node, (
                f"raw `{d}(...)` outside parallel/comms.py — route the "
                "collective through the one-home comms module so "
                "split_comms/hist_comms_dtype apply and the "
                "hist_allreduce_bytes payload model stays true to the "
                "wire (docs/ANALYSIS.md one-home-collective)"))
        self.generic_visit(node)


AST_CHECKERS = [
    TracedBranchChecker,
    HostSyncChecker,
    DtypeDriftChecker,
    CollectiveAxisChecker,
    BroadExceptChecker,
    NoPrintChecker,
    PallasInterpretChecker,
    PallasVmemGuardChecker,
    NamedScopeChecker,
    AtomicArtifactWriteChecker,
    RawPhaseTimingChecker,
    ServeBlockingIOChecker,
    OneHomeCollectiveChecker,
    # ddtlint v2 flow-aware passes (ISSUE 13): the sharding-spec
    # contract and the serve-tier thread/lock-discipline analysis.
    *shardspec.CHECKERS,
    threadmodel.ThreadModelChecker,
    # ddtlint v3 contract passes (ISSUE 16): config-flow cache-key /
    # fingerprint coverage and the mechanized telemetry schema.
    *configflow.CHECKERS,
    *telemetrycontract.CHECKERS,
]


# --------------------------------------------------------------------- #
# 6. suppression-hygiene  (not AST — .supp files)
# --------------------------------------------------------------------- #
SUPPRESSION_RULE = "suppression-hygiene"
#: suppression patterns scoped to our own kernels are self-justifying
_SCOPED_PREFIX = "ddt_"


def is_process_wide_suppression(line: str) -> bool:
    """Is a sanitizer-suppression entry (`race:PATTERN`, ...) process-wide,
    i.e. NOT scoped to one of our own kernel symbols?  Single source of
    truth shared with tsan_audit.write_audit_supp — the hygiene rule and
    the mechanized audit must classify entries identically, or the audited
    configuration stops matching what the gate enforces."""
    _, _, pattern = line.strip().partition(":")
    return not pattern.startswith(_SCOPED_PREFIX)


def check_suppressions(path: str, text: str) -> list[Finding]:
    """Sanitizer suppression hygiene: every PROCESS-WIDE entry (pattern not
    scoped to a ddt_ kernel symbol) must carry a structured `# AUDIT:` tag
    in its preceding comment block, naming how the suppression is
    re-verified (`make tsan-audit` reruns the soak without these entries
    and shape-checks the survivors).  Consecutive suppression lines share
    the comment block above them."""
    findings: list[Finding] = []
    block: list[str] = []                  # current comment block
    prev_was_comment = False
    for i, raw in enumerate(text.splitlines(), start=1):
        line = raw.strip()
        if not line:
            prev_was_comment = False
            continue
        if line.startswith("#"):
            if not prev_was_comment:
                block = []
            block.append(line)
            prev_was_comment = True
            continue
        prev_was_comment = False
        if ":" not in line:
            continue
        if not is_process_wide_suppression(line):
            continue
        if not any("AUDIT:" in c for c in block):
            findings.append(Finding(
                rule=SUPPRESSION_RULE, path=path, line=i, col=1,
                message=(
                    f"process-wide suppression `{line}` lacks a structured "
                    "`# AUDIT:` tag in its comment block — unscoped "
                    "frame-matches can hide real races (e.g. a kernel "
                    "returning before its workers finish); tag it with the "
                    "re-verification procedure (`make tsan-audit`)"),
                line_text=line,
            ))
    return findings
