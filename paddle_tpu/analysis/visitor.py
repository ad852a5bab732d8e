"""tpu_lint level-1 engine: per-file AST index + the rule driver.

One parse per file builds everything the rules query:

- a scope table of every function/method (qualnames, params, nested defs)
  and a same-file call graph, from which **step()-reachability** is computed
  (the "hot path" TPL001/TPL005 guard: everything the engine's `step()` can
  reach on the host side);
- a **device-value taint** pass over hot functions: values produced by
  device dispatches (`jnp.*`/`jax.*` calls, `*_fn`/`*_impl` executables) are
  tracked through assignments; scalarizations (`int()`, `.item()`, implicit
  `bool()`) and bulk fetches or waits (`np.asarray`, `jax.device_get`,
  `jax.block_until_ready`) of tainted values become sync events, annotated
  with whether they sit inside a `RecordEvent`/`_span` context;
- every **jit/shard_map call site** (incl. local aliases like the engine's
  `jit_ =` wrapper and `functools.partial(jax.jit, ...)` decorators), with
  the jitted function resolved to its def where possible so donation and
  traced-branch checks see real parameter lists;
- broad `except` handlers whose try body contains device calls.

Everything is stdlib-only (ast + tokenize-free): level 1 must lint a file in
milliseconds with no jax import.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .rules import AST_RULES, Finding, Suppressions

# functions whose bodies (and same-file transitive callees) are "hot":
# the serving engine's scheduler loop
HOT_ROOTS = frozenset({"step"})

# calls that produce device values (taint sources)
_DEVICE_CALL_RE = re.compile(
    r"(^|\.)((jax|jnp)\.)|(_fn|_impl)$|(^|\.)pallas_call$")
# calls that fetch a device value to the host (bulk, legitimate, must be
# spanned) vs. scalarize it (per-element, TPL001)
_FETCH_FUNCS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                          "numpy.array", "jax.device_get",
                          "jax.block_until_ready"})
_SCALARIZE_FUNCS = frozenset({"float", "int", "bool", "complex"})
# span context managers: entering one of these `with` blocks times the sync
_SPAN_CALL_RE = re.compile(r"(^|\.)(_span|RecordEvent)$")

_JIT_FUNCS = frozenset({"jax.jit", "jit", "pjit", "jax.pjit", "_AotCache"})
_SHARD_RE = re.compile(r"(^|\.)shard_map$")

# parameter names treated as static/config (never traced data) in TPL004
_STATIC_PARAM_NAMES = frozenset({"self", "cls", "cfg", "config", "mesh",
                                 "axis_names", "in_specs", "out_specs"})


def dotted_name(node: ast.AST) -> Optional[str]:
    """`a.b.c` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclasses.dataclass
class FunctionInfo:
    qualname: str
    node: ast.AST                       # FunctionDef | AsyncFunctionDef | Lambda
    params: List[str]
    scope: str                          # enclosing qualname ("" = module)
    calls: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class JitSite:
    node: ast.Call
    kind: str                           # "jit" | "shard_map"
    qualname: str                       # enclosing function (lambdas stripped)
    fn_name: str                        # display name of the jitted callable
    fn_params: Optional[List[str]]      # resolved parameter list, if known
    fn_node: Optional[ast.AST]          # resolved def/lambda, if known
    donate: Optional[bool]              # has donate_argnums? None = unknown


@dataclasses.dataclass
class SyncEvent:
    node: ast.AST
    kind: str                           # "scalarize" | "fetch" | "implicit_bool"
    what: str                           # e.g. "int(...)", "np.asarray(...)"
    func: str                           # hot function qualname
    spanned: bool                       # inside a RecordEvent/_span `with`


@dataclasses.dataclass
class TracedBranch:
    node: ast.AST
    stmt: str                           # "if" | "while"
    param: str
    func: str


@dataclasses.dataclass
class BroadHandler:
    node: ast.AST
    caught: str                         # "Exception" | "<bare>"
    device_calls: Set[str]


@dataclasses.dataclass
class DoubleBufferHazard:
    node: ast.AST                       # the mutation (or its call site)
    method: str                         # public entry-point qualname
    what: str                           # description of the page-state write


def _params_of(node: ast.AST) -> List[str]:
    a = node.args
    names = [x.arg for x in getattr(a, "posonlyargs", [])] + \
            [x.arg for x in a.args] + [x.arg for x in a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _strip_lambdas(qualname: str) -> str:
    """Normalize `<lambda>`/`<locals>` segments so a jit call inside a lambda
    registers under its enclosing named function."""
    parts = [p for p in qualname.split(".")
             if p not in ("<lambda>", "<locals>")]
    return ".".join(parts)


class _Indexer(ast.NodeVisitor):
    """Single walk: scope table + per-function call lists + jit-ish sites."""

    def __init__(self):
        self.functions: Dict[str, FunctionInfo] = {}
        self.stack: List[str] = []              # qualname segments
        self.fn_stack: List[FunctionInfo] = []
        self.raw_jit_calls: List[Tuple[ast.Call, str]] = []  # (node, qualname)
        # (decorator node, decorated FunctionDef, its qualname)
        self.raw_jit_decorators: List[Tuple[ast.AST, ast.AST, str]] = []
        self.jit_aliases: Set[str] = set()      # names assigned jit-wrapper lambdas
        self.module_body: List[ast.stmt] = []

    # -- scope bookkeeping ---------------------------------------------------
    def _qual(self, name: str) -> str:
        return ".".join(self.stack + [name]) if self.stack else name

    def visit_Module(self, node):
        self.module_body = node.body
        self.generic_visit(node)

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def _visit_function(self, node, name):
        qn = self._qual(name)
        info = FunctionInfo(qn, node, _params_of(node), ".".join(self.stack))
        # first def wins on duplicate qualnames (overloads by `if` are rare)
        self.functions.setdefault(qn, info)
        # decorator-style jit sites (@jax.jit / @jax.jit(...) /
        # @functools.partial(jax.jit, ...)) — these never appear as a plain
        # jit *call* with the function as an argument, so collect them here
        # or TPL002/TPL003 are blind to them
        for dec in node.decorator_list:
            if self._is_jit_decorator(dec):
                self.raw_jit_decorators.append(
                    (dec, node, _strip_lambdas(qn)))
        self.stack.append(name)
        self.fn_stack.append(info)
        self.generic_visit(node)
        self.fn_stack.pop()
        self.stack.pop()

    @staticmethod
    def _is_jit_decorator(dec: ast.AST) -> bool:
        if isinstance(dec, ast.Call):
            fd = dotted_name(dec.func) or ""
            if fd in _JIT_FUNCS:
                return True             # @jax.jit(static_argnums=...)
            if fd.split(".")[-1] == "partial" and dec.args:
                return (dotted_name(dec.args[0]) or "") in _JIT_FUNCS
            return False
        return (dotted_name(dec) or "") in _JIT_FUNCS   # bare @jax.jit

    def visit_FunctionDef(self, node):
        self._visit_function(node, node.name)

    def visit_AsyncFunctionDef(self, node):
        self._visit_function(node, node.name)

    def visit_Lambda(self, node):
        self.stack.append("<lambda>")
        self.generic_visit(node)
        self.stack.pop()

    # -- calls ---------------------------------------------------------------
    def visit_Call(self, node):
        d = dotted_name(node.func)
        if d is not None and self.fn_stack:
            self.fn_stack[-1].calls.append(d)
        if d is not None:
            base = d.split(".")[-1]
            if d in _JIT_FUNCS or base in ("_AotCache",) or \
                    d in self.jit_aliases or _SHARD_RE.search(d):
                self.raw_jit_calls.append(
                    (node, _strip_lambdas(".".join(self.stack))))
        self.generic_visit(node)

    def visit_Assign(self, node):
        # detect jit-wrapper aliases: `jit_ = (lambda fn, donate: jax.jit(...))
        # if mp else (lambda ...)` — calls through the alias are jit sites
        src = ast.dump(node.value)
        if "jax" in src and ("'jit'" in src or "_AotCache" in src):
            has_jit = any(
                isinstance(c, ast.Call) and
                (dotted_name(c.func) in _JIT_FUNCS or
                 (dotted_name(c.func) or "").split(".")[-1] == "_AotCache")
                for c in ast.walk(node.value))
            if has_jit:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and \
                            isinstance(node.value, (ast.Lambda, ast.IfExp)):
                        self.jit_aliases.add(tgt.id)
        self.generic_visit(node)


class ModuleIndex:
    """Queryable index of one parsed module."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        ix = _Indexer()
        # two passes so alias calls textually before/after the alias def both
        # resolve (class bodies execute out of line anyway)
        ix.visit(tree)
        if ix.jit_aliases:
            ix2 = _Indexer()
            ix2.jit_aliases = ix.jit_aliases
            ix2.visit(tree)
            ix = ix2
        self.functions = ix.functions
        self.jit_aliases = ix.jit_aliases
        self._raw_jit_calls = ix.raw_jit_calls
        self.jit_sites = [self._make_site(n, q) for n, q in ix.raw_jit_calls]
        self.jit_sites += [self._make_decorator_site(d, f, q)
                           for d, f, q in ix.raw_jit_decorators]
        self.jitted_fn_nodes = self._collect_jitted()

    # -- function resolution -------------------------------------------------
    def resolve_function(self, name: str, scope: str) -> Optional[FunctionInfo]:
        """Look `name` up as a nested def of `scope` (walking outward), a
        method of the enclosing class, then a module-level function."""
        parts = scope.split(".") if scope else []
        for i in range(len(parts), -1, -1):
            qn = ".".join(parts[:i] + [name])
            if qn in self.functions:
                return self.functions[qn]
        return None

    def _resolve_callable(self, node: ast.AST, scope: str
                          ) -> Tuple[str, Optional[FunctionInfo], Optional[ast.AST]]:
        """(display name, FunctionInfo|None, node|None) for a jit argument."""
        if isinstance(node, ast.Lambda):
            info = FunctionInfo("<lambda>", node, _params_of(node), scope)
            return "<lambda>", info, node
        d = dotted_name(node)
        if d is not None and "." not in d:
            info = self.resolve_function(d, scope)
            return d, info, info.node if info else None
        if isinstance(node, ast.Call):
            fd = dotted_name(node.func) or ""
            if fd.split(".")[-1] == "partial" and node.args:
                # functools.partial(f, ...) -> resolve f; partial-bound
                # leading args are dropped from the effective signature
                name, info, fnode = self._resolve_callable(node.args[0], scope)
                if info is not None:
                    bound = len(node.args) - 1
                    kw = {k.arg for k in node.keywords if k.arg}
                    params = [p for p in info.params[bound:] if p not in kw]
                    info = FunctionInfo(info.qualname, info.node, params,
                                        info.scope)
                return f"partial({name})", info, fnode
        return d or "<expr>", None, None

    def _make_site(self, node: ast.Call, qualname: str) -> JitSite:
        d = dotted_name(node.func) or ""
        kind = "shard_map" if _SHARD_RE.search(d) else "jit"
        fn_name, info, fn_node = ("<none>", None, None)
        if node.args:
            fn_name, info, fn_node = self._resolve_callable(node.args[0],
                                                            qualname)
        donate: Optional[bool] = None
        if kind == "jit":
            donate = any(k.arg in ("donate_argnums", "donate_argnames")
                         for k in node.keywords)
            if not donate and d in self.jit_aliases and len(node.args) >= 2:
                donate = True       # alias signature: (fn, donate_argnums, ...)
            elif not donate and d not in self.jit_aliases:
                donate = False
        return JitSite(node, kind, qualname, fn_name,
                       info.params if info else None, fn_node, donate)

    def _make_decorator_site(self, dec: ast.AST, fn_node: ast.AST,
                             qualname: str) -> JitSite:
        """@jax.jit-style decoration: the decorated def IS the jitted fn; the
        site registers under the function's own qualname."""
        donate = False
        if isinstance(dec, ast.Call):
            donate = any(k.arg in ("donate_argnums", "donate_argnames")
                         for k in dec.keywords)
        return JitSite(dec, "jit", qualname, fn_node.name,
                       _params_of(fn_node), fn_node, donate)

    def _collect_jitted(self) -> List[Tuple[ast.AST, List[str], str]]:
        """(fn node, data params, display name) for every function that gets
        traced: jit/shard_map arguments plus @jit-style decorators."""
        out = []
        seen = set()
        for site in self.jit_sites:
            if site.fn_node is not None and id(site.fn_node) not in seen:
                seen.add(id(site.fn_node))
                out.append((site.fn_node, site.fn_params or [],
                            f"{site.qualname or '<module>'}::{site.fn_name}"))
        for info in self.functions.values():
            node = info.node
            for dec in getattr(node, "decorator_list", []):
                dd = dotted_name(dec) or ""
                if isinstance(dec, ast.Call):
                    dd = dotted_name(dec.func) or ""
                    if dd.split(".")[-1] == "partial" and dec.args:
                        dd = dotted_name(dec.args[0]) or ""
                if dd in _JIT_FUNCS and id(node) not in seen:
                    seen.add(id(node))
                    out.append((node, info.params, info.qualname))
        return out

    # -- hot-path reachability ----------------------------------------------
    def hot_functions(self, roots: Iterable[str] = HOT_ROOTS
                      ) -> List[FunctionInfo]:
        """Functions reachable (same-file call graph) from any function whose
        bare name is in `roots`.  Edges: `self.m()` / `cls.m()` -> any method
        `m` in this module; bare `f()` -> nested def or module function."""
        by_bare: Dict[str, List[FunctionInfo]] = {}
        for info in self.functions.values():
            by_bare.setdefault(info.qualname.split(".")[-1], []).append(info)
        work = [f for r in roots for f in by_bare.get(r, [])]
        reached: Dict[str, FunctionInfo] = {f.qualname: f for f in work}
        while work:
            fn = work.pop()
            for call in fn.calls:
                parts = call.split(".")
                if len(parts) == 2 and parts[0] in ("self", "cls"):
                    cands = by_bare.get(parts[1], [])
                elif len(parts) == 1:
                    target = self.resolve_function(parts[0], fn.qualname)
                    cands = [target] if target else []
                else:
                    cands = []
                for c in cands:
                    if c.qualname not in reached:
                        reached[c.qualname] = c
                        work.append(c)
        return list(reached.values())


# ---------------------------------------------------------------------------
# device-value taint over hot functions
# ---------------------------------------------------------------------------


class _TaintPass:
    """Forward pass over a hot function's statements: track names bound to
    device dispatch results; emit sync events when they are scalarized,
    bool()-ed, or bulk-fetched (with span context)."""

    def __init__(self, finfo: FunctionInfo):
        self.finfo = finfo
        self.tainted: Set[str] = set()
        self.events: List[SyncEvent] = []

    # -- expression queries --------------------------------------------------
    def _is_device_call(self, call: ast.Call) -> bool:
        d = dotted_name(call.func)
        return bool(d and _DEVICE_CALL_RE.search(d)
                    and d not in _FETCH_FUNCS
                    and not _SPAN_CALL_RE.search(d))

    def _expr_tainted(self, node: ast.AST) -> bool:
        """Whether `node` evaluates to (or through) a device value.  Fetch and
        scalarize calls are opaque: `int(np.asarray(x)[0])` is ONE sync (the
        asarray), and its result is host data — looking through them would
        double-count every laundered value."""
        if isinstance(node, (ast.Name, ast.Attribute)):
            d = dotted_name(node)
            if d in self.tainted:
                return True
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d in _FETCH_FUNCS or d in _SCALARIZE_FUNCS or \
                    (isinstance(node.func, ast.Attribute) and
                     node.func.attr == "item"):
                return False            # sync boundary: result is host data
            if self._is_device_call(node):
                return True
        return any(self._expr_tainted(c) for c in ast.iter_child_nodes(node))

    def _sync_kind(self, call: ast.Call) -> Optional[Tuple[str, str]]:
        """(kind, label) when `call` is a sync op on a tainted value."""
        d = dotted_name(call.func)
        if d in _FETCH_FUNCS and call.args and \
                self._expr_tainted(call.args[0]):
            return "fetch", f"{d}(...)"
        if d in _SCALARIZE_FUNCS and call.args and \
                self._expr_tainted(call.args[0]):
            return "scalarize", f"{d}(...)"
        if isinstance(call.func, ast.Attribute) and call.func.attr == "item" \
                and self._expr_tainted(call.func.value):
            return "scalarize", ".item()"
        return None

    def _scan_expr(self, node: ast.AST, span: int) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                sk = self._sync_kind(sub)
                if sk is not None:
                    kind, what = sk
                    if kind == "fetch" and span > 0:
                        continue        # timed fetch: exactly what we want
                    self.events.append(SyncEvent(sub, kind, what,
                                                 self.finfo.qualname,
                                                 span > 0))

    # -- statement walk ------------------------------------------------------
    def _assign(self, targets: Sequence[ast.AST], value: ast.AST) -> None:
        rhs_tainted = self._expr_tainted(value)
        if isinstance(value, ast.Call) and self._sync_kind(value) is not None:
            rhs_tainted = False         # the sync resolved it to host data
        names: List[str] = []
        for t in targets:
            if isinstance(t, ast.Tuple):
                names += [dotted_name(e) for e in t.elts]
            else:
                names.append(dotted_name(t))
        for n in names:
            if n is None:
                continue
            if rhs_tainted:
                self.tainted.add(n)
            else:
                self.tainted.discard(n)

    def _is_span_with(self, item: ast.withitem) -> bool:
        if isinstance(item.context_expr, ast.Call):
            d = dotted_name(item.context_expr.func)
            return bool(d and _SPAN_CALL_RE.search(d))
        return False

    def walk(self, body: Sequence[ast.stmt], span: int = 0) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.Assign,)):
                self._scan_expr(stmt.value, span)
                self._assign(stmt.targets, stmt.value)
            elif isinstance(stmt, ast.AugAssign):
                self._scan_expr(stmt.value, span)
                if self._expr_tainted(stmt.value):
                    n = dotted_name(stmt.target)
                    if n:
                        self.tainted.add(n)
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                self._scan_expr(stmt.value, span)
                self._assign([stmt.target], stmt.value)
            elif isinstance(stmt, ast.Expr):
                self._scan_expr(stmt.value, span)
            elif isinstance(stmt, (ast.If, ast.While)):
                self._scan_expr(stmt.test, span)
                if self._expr_tainted(stmt.test) and not (
                        isinstance(stmt.test, ast.Call) and
                        self._sync_kind(stmt.test)):
                    self.events.append(SyncEvent(
                        stmt.test, "implicit_bool", "if/while test",
                        self.finfo.qualname, span > 0))
                self.walk(stmt.body, span)
                self.walk(stmt.orelse, span)
            elif isinstance(stmt, ast.For):
                self._scan_expr(stmt.iter, span)
                if self._expr_tainted(stmt.iter):
                    self._assign([stmt.target], stmt.iter)
                self.walk(stmt.body, span)
                self.walk(stmt.orelse, span)
            elif isinstance(stmt, ast.With):
                entered = span + (1 if any(self._is_span_with(i)
                                           for i in stmt.items) else 0)
                for i in stmt.items:
                    if not self._is_span_with(i):
                        self._scan_expr(i.context_expr, span)
                self.walk(stmt.body, entered)
            elif isinstance(stmt, ast.Try):
                self.walk(stmt.body, span)
                for h in stmt.handlers:
                    self.walk(h.body, span)
                self.walk(stmt.orelse, span)
                self.walk(stmt.finalbody, span)
            elif isinstance(stmt, (ast.Return, ast.Raise)) and \
                    getattr(stmt, "value", None) is not None:
                self._scan_expr(stmt.value, span)
            # nested defs are separate functions; the call graph carries them


def _hot_sync_events(index: ModuleIndex) -> List[SyncEvent]:
    events: List[SyncEvent] = []
    for finfo in index.hot_functions():
        if not isinstance(finfo.node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
            continue
        tp = _TaintPass(finfo)
        tp.walk(finfo.node.body)
        events.extend(e for e in tp.events if not e.spanned)
    return events


# ---------------------------------------------------------------------------
# traced-branch detection (TPL004)
# ---------------------------------------------------------------------------


def _traced_branches(index: ModuleIndex) -> List[TracedBranch]:
    out = []
    for fn_node, params, display in index.jitted_fn_nodes:
        data = [p for p in params if p not in _STATIC_PARAM_NAMES]
        if not data or isinstance(fn_node, ast.Lambda):
            continue
        for stmt in ast.walk(fn_node):
            if not isinstance(stmt, (ast.If, ast.While)):
                continue
            param = _branch_on_param(stmt.test, set(data))
            if param is not None:
                out.append(TracedBranch(
                    stmt, "if" if isinstance(stmt, ast.If) else "while",
                    param, display))
    return out


def _branch_on_param(test: ast.AST, data: Set[str]) -> Optional[str]:
    """The offending parameter name when `test` branches on a traced value;
    None when every reference is statically safe (shape/dtype access,
    `is None`, len/isinstance)."""
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(test):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node
    for node in ast.walk(test):
        if not (isinstance(node, ast.Name) and node.id in data):
            continue
        p = parents.get(id(node))
        safe = False
        hops = 0
        cur, prev = p, node
        while cur is not None and hops < 6:
            if isinstance(cur, ast.Attribute) and cur.value is prev:
                safe = True             # x.shape / x.dtype / x.ndim — static
                break
            if isinstance(cur, ast.Compare) and \
                    all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in cur.ops):
                safe = True             # x is None
                break
            if isinstance(cur, ast.Call):
                fd = dotted_name(cur.func) or ""
                if fd.split(".")[-1] in ("len", "isinstance", "getattr",
                                         "hasattr", "callable"):
                    safe = True         # static under tracing
                    break
            prev, cur = cur, parents.get(id(cur))
            hops += 1
        if not safe:
            return node.id
    return None


# ---------------------------------------------------------------------------
# double-buffer hazards (TPL007)
# ---------------------------------------------------------------------------

# page-state mutators: calls that free/reassign KV pages or stores into the
# per-slot length/table/refcount arrays.  A public entry point of a
# double-buffered engine must harvest the in-flight batch before any of
# these run, or the in-flight dispatch's KV writes land in pages the host
# has already handed to someone else (the invariant `abort()` relies on).
_PAGE_MUTATOR_ATTRS = frozenset({"release", "allocate", "allocate_prefixed"})
_PAGE_STATE_ATTRS = frozenset({"lengths", "page_table", "refcounts",
                               "ref_counts"})


def _publishes_inflight(info: FunctionInfo) -> bool:
    """Whether this function assigns a non-None value to `self._inflight` —
    the double-buffering marker (the step parks its un-synced dispatch
    there; `None` assignments are the harvest clearing it)."""
    for node in ast.walk(info.node):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if dotted_name(t) == "self._inflight" and not (
                        isinstance(node.value, ast.Constant) and
                        node.value.value is None):
                    return True
    return False


def _direct_mutations(info: FunctionInfo) -> List[Tuple[ast.AST, str]]:
    """(node, description) for every direct page-state mutation in `info`."""
    out: List[Tuple[ast.AST, str]] = []
    for node in ast.walk(info.node):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _PAGE_MUTATOR_ATTRS:
            out.append((node, f".{node.func.attr}()"))
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Subscript) and \
                        isinstance(t.value, ast.Attribute) and \
                        t.value.attr in _PAGE_STATE_ATTRS:
                    out.append((node, f".{t.value.attr}[...] store"))
    return out


def _direct_harvests(info: FunctionInfo) -> List[ast.AST]:
    return [node for node in ast.walk(info.node)
            if isinstance(node, ast.Call) and
            (dotted_name(node.func) or "").split(".")[-1] == "_harvest"]


def _double_buffer_hazards(index: ModuleIndex) -> List[DoubleBufferHazard]:
    """Public methods of a double-buffered class that (transitively, same
    file) mutate page-table/refcount state BEFORE any harvest of the
    in-flight batch.  Position is compared by line number: the mutation's
    position is its own line for a direct write, or the call site's line
    when it happens inside a callee — so `step()`'s harvest-at-the-top
    pattern and `abort()`'s harvest-guard both pass, and a tie (one call
    that both harvests and mutates, like `run()` -> `step()`) passes too."""
    classes = {info.scope for info in index.functions.values()
               if info.scope and _publishes_inflight(info)}
    if not classes:
        return []
    hazards: List[DoubleBufferHazard] = []
    for cls in classes:
        methods = {i.qualname.split(".")[-1]: i
                   for i in index.functions.values() if i.scope == cls}

        def closure(name: str) -> Set[str]:
            seen: Set[str] = set()
            work = [name]
            while work:
                cur = work.pop()
                info = methods.get(cur)
                if info is None or cur in seen:
                    continue
                seen.add(cur)
                for call in info.calls:
                    parts = call.split(".")
                    if len(parts) == 2 and parts[0] in ("self", "cls") and \
                            parts[1] in methods:
                        work.append(parts[1])
            return seen

        mutates = {name: bool(_direct_mutations(i))
                   for name, i in methods.items()}
        harvests = {name: bool(_direct_harvests(i))
                    for name, i in methods.items()}
        for name, info in methods.items():
            if name.startswith("_") or not isinstance(
                    info.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            first_mut: Optional[Tuple[int, ast.AST, str]] = None
            first_harv: Optional[int] = None
            for node, what in _direct_mutations(info):
                ln = getattr(node, "lineno", 1)
                if first_mut is None or ln < first_mut[0]:
                    first_mut = (ln, node, what)
            for node in _direct_harvests(info):
                ln = getattr(node, "lineno", 1)
                if first_harv is None or ln < first_harv:
                    first_harv = ln
            # call sites into mutating / harvesting callees
            for node in ast.walk(info.node):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                parts = (d or "").split(".")
                if len(parts) == 2 and parts[0] in ("self", "cls"):
                    callee = parts[1]
                    ln = getattr(node, "lineno", 1)
                    sub = closure(callee)
                    if any(mutates.get(m) for m in sub):
                        if first_mut is None or ln < first_mut[0]:
                            first_mut = (ln, node, f"via self.{callee}()")
                    if any(m == "_harvest" or harvests.get(m) for m in sub):
                        if first_harv is None or ln < first_harv:
                            first_harv = ln
            if first_mut is not None and (first_harv is None or
                                          first_harv > first_mut[0]):
                hazards.append(DoubleBufferHazard(
                    first_mut[1], info.qualname, first_mut[2]))
    return hazards


# ---------------------------------------------------------------------------
# broad except handlers around device code (TPL006)
# ---------------------------------------------------------------------------


# TPL006 uses a stricter device pattern than the taint pass: `*_fn` names in
# try bodies are usually user callbacks (collate_fn, init_fn), not dispatches
_TRY_DEVICE_RE = re.compile(r"^(jax|jnp)\.|(^|\.)pallas_call$")


def _broad_device_handlers(tree: ast.Module) -> List[BroadHandler]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        device_calls: Set[str] = set()
        for sub in ast.walk(ast.Module(body=node.body, type_ignores=[])):
            if isinstance(sub, ast.Call):
                d = dotted_name(sub.func)
                if d and _TRY_DEVICE_RE.search(d):
                    device_calls.add(d)
        if not device_calls:
            continue
        for h in node.handlers:
            caught = None
            if h.type is None:
                caught = "<bare>"
            else:
                types = h.type.elts if isinstance(h.type, ast.Tuple) \
                    else [h.type]
                if any((dotted_name(t) or "").split(".")[-1] in
                       ("Exception", "BaseException") for t in types):
                    caught = dotted_name(h.type) if not isinstance(
                        h.type, ast.Tuple) else "Exception"
            if caught:
                out.append(BroadHandler(h, caught, device_calls))
    return out


# ---------------------------------------------------------------------------
# file context + driver
# ---------------------------------------------------------------------------


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _relpath(path: str) -> str:
    """Repo-relative '/'-separated path (registry key form); paths outside
    the repo stay as given."""
    rel = os.path.relpath(os.path.abspath(path), repo_root())
    return path if rel.startswith("..") else rel.replace(os.sep, "/")


class FileContext:
    """Everything the rules need about one file, built once."""

    def __init__(self, path: str, source: str, registry) -> None:
        self.path = path
        self.relpath = _relpath(path)
        self.source = source
        self.registry = registry
        self.suppressions = Suppressions(source)
        tree = ast.parse(source, filename=path)
        self.index = ModuleIndex(tree)
        self.jit_sites = self.index.jit_sites
        self.hot_sync_events = _hot_sync_events(self.index)
        self.traced_branches = _traced_branches(self.index)
        self.broad_device_handlers = _broad_device_handlers(tree)
        self.db_hazards = _double_buffer_hazards(self.index)


def iter_python_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in sorted(dirnames)
                               if d not in ("__pycache__", ".git")]
                out.extend(os.path.join(dirpath, f)
                           for f in sorted(filenames) if f.endswith(".py"))
        elif p.endswith(".py"):
            out.append(p)
    return out


def run_ast_checks(paths: Sequence[str], rules=None,
                   registry=None) -> List[Finding]:
    """Level 1: run every AST rule over the python files under `paths`.
    Returns ALL findings; suppressed ones carry suppressed=True.  `registry`
    defaults to `analysis.registry` (injectable for fixture tests)."""
    if registry is None:
        from . import registry as registry_mod
        registry = registry_mod
    rules = list(rules) if rules is not None else list(AST_RULES)
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        try:
            ctx = FileContext(path, source, registry)
        except SyntaxError as e:
            findings.append(Finding("LINT001", path, e.lineno or 1, 0,
                                    f"syntax error: {e.msg}"))
            continue
        file_findings: List[Finding] = []
        for rule in rules:
            file_findings.extend(rule.check(ctx))
        findings.extend(ctx.suppressions.apply(file_findings))
    # orphaned registry entries: a declared program source whose FILE is gone
    # (deleted/renamed) never gets a FileContext, so the per-file stale check
    # above cannot see it — sweep every entry under the linted directories
    linted = {_relpath(p) for p in iter_python_files(paths)}
    # absolute-path containment, not relpath string prefixes: roots spelled
    # as '.', 'paddle_tpu/', or an ancestor must all cover the same entries
    dir_roots = [os.path.abspath(p) for p in paths if os.path.isdir(p)]
    for entry in getattr(registry, "PROGRAM_SOURCES", ()):
        if entry.path in linted:
            continue
        entry_abs = os.path.abspath(
            entry.path if os.path.isabs(entry.path)
            else os.path.join(repo_root(), entry.path))
        if any(entry_abs.startswith(root + os.sep) for root in dir_roots):
            findings.append(Finding(
                "TPL002", entry.path, 1, 0,
                f"registry entry `{entry.qualname or '<module>'}` declares a "
                f"program source in a file that no longer exists — remove it "
                f"from analysis/registry.py"))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
