"""Pass 2 — JAX hot-path lint for crypto/ and parallel/.

Walks the AST of every module under ouroboros_tpu/crypto and
ouroboros_tpu/parallel, computes the set of *traced* functions (jitted
directly, passed to jax.jit / lax control-flow / vmap / shard_map, or
reachable from one through same-module calls), and flags host-sync and
retrace hazards inside those bodies:

- JAX001 host-conversion: int()/float()/bool() applied to a non-static
  expression inside a traced body — forces a device sync (or a tracer
  error) at run time.
- JAX002 item-sync: `.item()` inside a traced body — a blocking
  device->host transfer per element.
- JAX003 numpy-in-jit: `np.*` / `numpy.*` call inside a traced body —
  either a silent trace-time constant or a tracer TypeError; hot paths
  must use jnp/lax.
- JAX004 jit-per-call: `jax.jit(...)` constructed inside a function body
  that is not memoised (functools.lru_cache/functools.cache) — a fresh
  jit wrapper (and XLA compile) every invocation.
- JAX005 lambda-to-jit: a known-jitted callable invoked with an inline
  lambda argument — a fresh function object per call, so the jit cache
  can never hit (and a tracer error unless marked static).
- JAX006 jit-in-loop: jax.jit / shard_map / pallas_call CONSTRUCTED
  lexically inside a for/while loop — a per-window or per-rep kernel
  rebuild, a retrace every time round.
  Memoised builders called from loops are fine; building the wrapper in
  the loop body never is.

The traced-set computation is deliberately same-module only: cross-module
calls (e.g. field_jax helpers) are linted in their own module when they
are jitted/traced there, which keeps the pass O(files) with no import cost.
The scan covers crypto/ and parallel/.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from . import Finding, register, relpath
from .astutil import QualnameVisitor, dotted_name, iter_py_files, parse_file

SCAN_DIRS = ("ouroboros_tpu/crypto", "ouroboros_tpu/parallel")

_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}
# Calls whose function-valued arguments are traced when invoked.
_TRACING_CALLS = {
    "jax.jit", "jit", "jax.pjit", "pjit",
    "jax.vmap", "vmap", "jax.pmap", "pmap",
    "jax.grad", "jax.value_and_grad", "jax.checkpoint", "jax.remat",
    "jax.shard_map", "shard_map",
    "lax.fori_loop", "jax.lax.fori_loop",
    "lax.scan", "jax.lax.scan",
    "lax.while_loop", "jax.lax.while_loop",
    "lax.cond", "jax.lax.cond",
    "lax.switch", "jax.lax.switch",
    "lax.map", "jax.lax.map",
    "lax.associative_scan", "jax.lax.associative_scan",
}
_CACHE_DECORATORS = {"functools.lru_cache", "lru_cache",
                     "functools.cache", "cache"}
# kernel-wrapper constructions JAX006 watches inside loop bodies
_KERNEL_BUILDERS = _JIT_NAMES | {
    "jax.shard_map", "shard_map",
    "pl.pallas_call", "pltpu.pallas_call", "pallas_call",
}
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype"}


def _call_name(node: ast.Call) -> Optional[str]:
    return dotted_name(node.func)


def _is_static_expr(node: ast.AST) -> bool:
    """Expressions whose int()/bool() conversion is trace-safe: literals,
    len(), and shape/dtype metadata (plus arithmetic over those)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Call):
        name = _call_name(node)
        if name == "len":
            return True
        return False
    if isinstance(node, ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return True
        return False
    if isinstance(node, ast.Subscript):
        return _is_static_expr(node.value)
    if isinstance(node, ast.BinOp):
        return _is_static_expr(node.left) and _is_static_expr(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_static_expr(node.operand)
    return False


def _decorator_jits(dec: ast.AST) -> bool:
    name = dotted_name(dec)
    if name in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        fname = dotted_name(dec.func)
        if fname in _JIT_NAMES:
            return True            # @jax.jit(static_argnums=...)
        if fname in ("functools.partial", "partial") and dec.args:
            return dotted_name(dec.args[0]) in _JIT_NAMES
    return False


def _decorator_caches(dec: ast.AST) -> bool:
    name = dotted_name(dec)
    if name in _CACHE_DECORATORS:
        return True
    if isinstance(dec, ast.Call):
        return dotted_name(dec.func) in _CACHE_DECORATORS
    return False


class _ModuleIndex(ast.NodeVisitor):
    """First sweep: function defs by bare name, traced roots, call graph."""

    def __init__(self):
        self.defs: Dict[str, List[ast.AST]] = {}
        self.roots: Set[str] = set()       # bare names of traced functions
        self.traced_lambdas: List[ast.Lambda] = []
        self.calls: Dict[str, Set[str]] = {}   # caller bare name -> callees
        self.jitted_names: Set[str] = set()    # names wrapped by jax.jit
        self.enclosing: Dict[int, tuple] = {}  # def node id -> outer defs
        self._stack: List[str] = []

    def _visit_def(self, node):
        self.defs.setdefault(node.name, []).append(node)
        self.enclosing[id(node)] = tuple(self._stack)
        if any(_decorator_jits(d) for d in node.decorator_list):
            self.roots.add(node.name)
            self.jitted_names.add(node.name)   # the def IS the jit wrapper
        self._stack.append(node.name)
        try:
            self.generic_visit(node)
        finally:
            self._stack.pop()

    visit_FunctionDef = _visit_def
    visit_AsyncFunctionDef = _visit_def

    def visit_Call(self, node: ast.Call):
        name = _call_name(node)
        if self._stack:
            caller = self._stack[-1]
            if isinstance(node.func, ast.Name):
                self.calls.setdefault(caller, set()).add(node.func.id)
        if name in _TRACING_CALLS:
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    self.roots.add(arg.id)
                elif isinstance(arg, ast.Lambda):
                    self.traced_lambdas.append(arg)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign):
        # `fast = jax.jit(f)`: calls to `fast` hit the jit cache, so THAT
        # is the name JAX005 watches (not the raw `f`, which stays a
        # plain Python callable).
        if isinstance(node.value, ast.Call) and \
                _call_name(node.value) in _JIT_NAMES:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.jitted_names.add(t.id)
        self.generic_visit(node)

    def traced_set(self) -> Set[str]:
        """Closure of traced roots over the same-module call graph."""
        traced = set(self.roots)
        frontier = list(traced)
        while frontier:
            fn = frontier.pop()
            for callee in self.calls.get(fn, ()):
                if callee in self.defs and callee not in traced:
                    traced.add(callee)
                    frontier.append(callee)
        return traced


class _TracedBodyLint(QualnameVisitor):
    """Flags JAX001/002/003 within one traced function subtree."""

    def __init__(self, file: str, findings: List[Finding], prefix: str):
        super().__init__()
        self.file = file
        self.findings = findings
        self._prefix = prefix

    def _add(self, node, rule, message):
        qn = self.qualname
        if qn == "<module>" or qn == self._prefix:
            symbol = self._prefix
        elif qn.startswith(self._prefix + "."):
            symbol = qn
        else:
            symbol = f"{self._prefix}.{qn}"
        self.findings.append(Finding(
            file=self.file, line=node.lineno, rule=rule,
            symbol=symbol, message=message))

    def visit_Call(self, node: ast.Call):
        name = _call_name(node)
        if name in ("int", "float", "bool") and node.args and \
                not _is_static_expr(node.args[0]):
            self._add(node, "JAX001",
                      f"{name}() on a traced value forces a host sync "
                      f"inside a jitted body")
        elif isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args:
            self._add(node, "JAX002",
                      ".item() inside a jitted body is a per-element "
                      "device->host transfer")
        elif name and (name.startswith("np.") or name.startswith("numpy.")):
            self._add(node, "JAX003",
                      f"{name}() inside a jitted body runs on host at "
                      f"trace time; use jnp/lax")
        self.generic_visit(node)


class _JitPerCallLint(QualnameVisitor):
    """Flags JAX004 (jit built per call), JAX005 (lambda into a jitted
    callable) and JAX006 (kernel wrapper built inside a loop) over the
    whole module."""

    def __init__(self, file: str, findings: List[Finding],
                 jitted_names: Set[str]):
        super().__init__()
        self.file = file
        self.findings = findings
        self.jitted_names = jitted_names
        self._cached_depth = 0
        self._fn_depth = 0
        self._loop_depth = 0

    def _visit_loop(self, node):
        self._loop_depth += 1
        try:
            self.generic_visit(node)
        finally:
            self._loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop

    def _visit_scope(self, node):
        cached = any(_decorator_caches(d) for d in node.decorator_list)
        self._cached_depth += cached
        self._fn_depth += isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef))
        # a def nested in a loop runs at CALL time, not per iteration:
        # its body starts from loop depth 0
        outer_loops, self._loop_depth = self._loop_depth, 0
        try:
            QualnameVisitor._visit_scope(self, node)
        finally:
            self._loop_depth = outer_loops
            self._cached_depth -= cached
            self._fn_depth -= isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef))

    visit_FunctionDef = _visit_scope
    visit_AsyncFunctionDef = _visit_scope

    def _add(self, node, rule, message):
        self.findings.append(Finding(
            file=self.file, line=node.lineno, rule=rule,
            symbol=self.qualname, message=message))

    def visit_Call(self, node: ast.Call):
        name = _call_name(node)
        if name in _KERNEL_BUILDERS and self._loop_depth > 0:
            self._add(node, "JAX006",
                      f"{name}() constructed inside a loop body rebuilds "
                      f"the kernel wrapper every iteration (per-window/"
                      f"per-rep retrace hazard); hoist the construction "
                      f"out of the loop or memoise the builder")
        if name in _JIT_NAMES:
            if self._fn_depth > 0 and self._cached_depth == 0:
                self._add(node, "JAX004",
                          "jax.jit() constructed inside an un-memoised "
                          "function body recompiles on every call; hoist "
                          "it or wrap the builder in functools.lru_cache")
        elif name is not None:
            bare = name.rsplit(".", 1)[-1]
            if bare in self.jitted_names and \
                    any(isinstance(a, ast.Lambda) for a in node.args):
                self._add(node, "JAX005",
                          f"inline lambda passed to jitted {bare}(): a "
                          f"fresh callable per call defeats the jit cache")
        self.generic_visit(node)


def lint_source(source: str, file: str) -> List[Finding]:
    """Run the JAX pass over one source text (fixture entry point)."""
    return _lint_tree(ast.parse(source, filename=file), file)


def _lint_tree(tree: ast.Module, file: str) -> List[Finding]:
    index = _ModuleIndex()
    index.visit(tree)
    traced = index.traced_set()
    findings: List[Finding] = []
    for name in sorted(traced):
        for node in index.defs.get(name, ()):
            # a def nested inside a traced def is covered by the outer
            # walk (symbol `outer.inner`); a standalone walk here would
            # report the same line twice under two symbols
            if any(enc in traced
                   for enc in index.enclosing.get(id(node), ())):
                continue
            lint = _TracedBodyLint(file, findings, prefix=name)
            for child in ast.iter_child_nodes(node):
                lint.visit(child)
    for lam in index.traced_lambdas:
        lint = _TracedBodyLint(file, findings, prefix="<lambda>")
        lint.visit(lam.body)
    _JitPerCallLint(file, findings, index.jitted_names).visit(tree)
    return sorted(set(findings))


def run_files(paths: Iterable[str]) -> List[Finding]:
    findings: List[Finding] = []
    for path in paths:
        findings.extend(_lint_tree(parse_file(path), relpath(path)))
    return findings


@register("jax")
def run() -> List[Finding]:
    return run_files(iter_py_files(*SCAN_DIRS))
