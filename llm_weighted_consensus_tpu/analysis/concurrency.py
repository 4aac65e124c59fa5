"""Shared machinery for the concurrency-discipline rules (LWC014–016).

The per-function lints never cross function boundaries; the concurrency
auditor must — a data race is by definition a property of two call
paths.  This module builds, once per parsed set, a ``ProjectIndex``:

* the **lock model** (``CONCURRENCY_MODEL``, declared by
  ``analysis/concurrency_model.py`` for the package or inline by a
  fixture — a parsed set that declares no model checks nothing, so
  single-file lint invocations stay self-contained);
* every **lock creation site** (``self.x = threading.Lock()`` et al.)
  for the both-ways registry check;
* per-function **lock facts**: which registered locks each statement
  lexically holds (``with`` nesting), and every acquisition event with
  the locks held at that instant — the static lock-order graph's raw
  edges;
* a name-resolved **call graph** with transitive lock closure, so
  "holding the gate, the dispatch calls into the staging pool which
  takes its own lock" becomes a visible order edge.  Resolution is
  deliberately over-approximate (attribute calls resolve to every
  same-named method/function in the package, minus a blacklist of
  container/stdlib method names that would wire dict.get to
  ``ChoiceIndexer.get``) — over-approximation can only add edges to
  declare, never hide a real one.  Local aliases (``fn = self._x``)
  and the batcher's ``getattr(self, "_dispatch_" + kind)`` prefix
  dispatch are resolved so the guarded dispatch path stays visible;
* **thread entry points**: ``threading.Thread(target=...)`` roots,
  executor ``submit``/``run_in_executor`` roots (an executor root
  counts double — every pool here has >= 2 workers), and the asyncio
  loop (all ``async def`` share ONE entry — the loop is one thread),
  propagated over the call graph.  A field whose accessing methods are
  reached from >= 2 entry weights is cross-thread state.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import ParsedModule, body_nodes

MODEL_NAME = "CONCURRENCY_MODEL"

# threading constructors that create a registrable primitive
_LOCK_CTORS = {"Lock", "RLock", "Condition"}
_CTOR_KIND = {"Lock": "lock", "RLock": "rlock", "Condition": "condition"}

# attribute names never resolved through the call graph: container /
# stdlib / future methods whose package-level namesakes (dict.get vs
# ChoiceIndexer.get) would wire false edges through every critical
# section that touches a dict
_GENERIC_ATTRS = {
    "get",
    "pop",
    "popleft",
    "append",
    "appendleft",
    "extend",
    "extendleft",
    "insert",
    "items",
    "keys",
    "values",
    "setdefault",
    "update",
    "add",
    "clear",
    "remove",
    "discard",
    "sort",
    "copy",
    "count",
    "index",
    "join",
    "split",
    "strip",
    "startswith",
    "endswith",
    "format",
    "encode",
    "decode",
    "lower",
    "upper",
    "replace",
    "put",
    "done",
    "cancel",
    "cancelled",
    "set",
    "is_set",
    "snapshot",
    "render",
    "total_seconds",
    # the context-manager protocol called by hand (obs/hostspan.py
    # enters the profiler's annotation so): a ``with`` statement is never
    # resolved through the graph, and the same call spelled out must
    # not resolve to every ``__exit__`` in the package either
    "__enter__",
    "__exit__",
}

# modules whose functions are never bare-name call-resolution TARGETS:
# the witness's proxies are injected dynamically (never statically
# reachable), and over-approximate resolution would otherwise wire
# every ``cond.wait()``/``lock.acquire()`` in the package into the
# proxy's bookkeeping (and its leaf mutex), fabricating order edges.
# Their own bodies are still indexed and checked (LWC014 guards the
# witness's fields; ``self.m()`` resolution inside them stays precise).
_OUT_OF_GRAPH_SUFFIXES = ("analysis/witness.py",)

_EXEMPT_RE = re.compile(
    r"#\s*caller-holds-lock:\s*(?P<lock>[\w.]+)\s*(?:[(\[—–-]\s*"
    r"(?P<reason>[^)\]]*\S)\s*[)\]]?)?"
)


# ---------------------------------------------------------------------------
# Lock model
# ---------------------------------------------------------------------------


@dataclass
class LockModel:
    locks: Dict[str, dict]
    order: List[Tuple[str, str]]
    order_runtime: List[tuple]
    module: ParsedModule
    line: int

    def in_scope(self, key: str, modules: Sequence[ParsedModule]) -> bool:
        """Whether the entry's declaring module is part of this parsed
        set — staleness is only judged when it is (single-file runs
        must not call every other entry stale)."""
        suffix = self.locks[key].get("module", "")
        return any(m.rel.endswith(suffix) for m in modules)

    def lock_for(self, class_name: str, attr: str) -> Optional[str]:
        key = f"{class_name}.{attr}"
        return key if key in self.locks else None

    def via(self) -> Dict[str, str]:
        """acquire_via method name -> lock key."""
        out: Dict[str, str] = {}
        for key, entry in self.locks.items():
            for name in entry.get("acquire_via", ()):
                out[name] = key
        return out


def load_model(modules: Sequence[ParsedModule]) -> Optional[LockModel]:
    """The parsed set's ``CONCURRENCY_MODEL`` literal, if any module
    declares one at module level (last declaration wins)."""
    found = None
    for module in modules:
        for node in module.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            if not any(
                isinstance(t, ast.Name) and t.id == MODEL_NAME
                for t in node.targets
            ):
                continue
            try:
                data = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                continue
            if isinstance(data, dict) and isinstance(
                data.get("locks"), dict
            ):
                found = LockModel(
                    locks=dict(data["locks"]),
                    order=[tuple(e) for e in data.get("order", ())],
                    order_runtime=[
                        tuple(e) for e in data.get("order_runtime", ())
                    ],
                    module=module,
                    line=node.lineno,
                )
    return found


@dataclass
class LockSite:
    """One ``<target> = threading.Lock()`` creation site."""

    key: str  # "Class.attr" (or bare name at module level)
    kind: str  # "lock" | "rlock" | "condition"
    module: ParsedModule
    node: ast.AST
    class_name: str  # "" for module-level locks


def _ctor_kind(value: ast.AST) -> Optional[str]:
    """``threading.Lock()`` / ``Lock()`` -> "lock"; None otherwise."""
    if not isinstance(value, ast.Call):
        return None
    func = value.func
    name = None
    if isinstance(func, ast.Attribute) and isinstance(
        func.value, ast.Name
    ):
        if func.value.id == "threading":
            name = func.attr
    elif isinstance(func, ast.Name):
        name = func.id
    return _CTOR_KIND.get(name) if name in _LOCK_CTORS else None


def lock_sites(modules: Sequence[ParsedModule]) -> List[LockSite]:
    """Every threading-primitive creation site in the parsed set."""
    sites: List[LockSite] = []
    for module in modules:
        # module-level: NAME = threading.Lock()
        for node in module.tree.body:
            if isinstance(node, ast.Assign):
                kind = _ctor_kind(node.value)
                if kind and isinstance(node.targets[0], ast.Name):
                    sites.append(
                        LockSite(
                            key=node.targets[0].id,
                            kind=kind,
                            module=module,
                            node=node,
                            class_name="",
                        )
                    )
        # instance fields: self.x = threading.Lock() in any method
        for cls in module.classes():
            for method in cls.methods:
                for node in body_nodes(method.node):
                    if not isinstance(node, ast.Assign):
                        continue
                    kind = _ctor_kind(node.value)
                    if kind is None:
                        continue
                    target = node.targets[0]
                    if (
                        isinstance(target, ast.Attribute)
                        and isinstance(target.value, ast.Name)
                        and target.value.id == "self"
                    ):
                        sites.append(
                            LockSite(
                                key=f"{cls.name}.{target.attr}",
                                kind=kind,
                                module=module,
                                node=node,
                                class_name=cls.name,
                            )
                        )
    return sites


# ---------------------------------------------------------------------------
# Per-function lock facts
# ---------------------------------------------------------------------------


@dataclass
class LockFacts:
    """Lexical lock state for one function body."""

    # (lock key, acquisition node, locks held just before)
    acquisitions: List[Tuple[str, ast.AST, Tuple[str, ...]]] = field(
        default_factory=list
    )
    # every body node with the registered locks lexically held there
    nodes: List[Tuple[ast.AST, Tuple[str, ...]]] = field(
        default_factory=list
    )


def _resolve_lock_expr(
    expr: ast.AST, class_name: str, model: LockModel, via: Dict[str, str]
) -> Optional[str]:
    """A with-item context expression (or acquire receiver) -> lock key.

    ``self._lock`` resolves inside the owning class; ``x.shared()`` /
    ``x.exclusive()`` / ``x.dispatch_guard()`` resolve through
    ``acquire_via``; a bare name resolves to a module-level lock key.
    """
    if isinstance(expr, ast.Call):
        if isinstance(expr.func, ast.Attribute):
            hit = via.get(expr.func.attr)
            if hit is not None:
                return hit
        if isinstance(expr.func, ast.Name):
            hit = via.get(expr.func.id)
            if hit is not None:
                return hit
        return None
    if (
        isinstance(expr, ast.Attribute)
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "self"
        and class_name
    ):
        return model.lock_for(class_name, expr.attr)
    if isinstance(expr, ast.Name) and expr.id in model.locks:
        return expr.id
    return None


def lock_facts(
    func_node: ast.AST,
    class_name: str,
    model: LockModel,
    via: Dict[str, str],
) -> LockFacts:
    facts = LockFacts()

    def note(node: ast.AST, held: Tuple[str, ...]) -> None:
        facts.nodes.append((node, held))
        # raw lock.acquire() call: an acquisition event for the order
        # graph (no held-region tracking — `with` is the idiom)
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "acquire"
        ):
            key = _resolve_lock_expr(
                node.func.value, class_name, model, via
            )
            if key is not None:
                facts.acquisitions.append((key, node, held))

    def visit(node: ast.AST, held: Tuple[str, ...]) -> None:
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            inner = held
            for item in node.items:
                for sub in ast.walk(item.context_expr):
                    note(sub, inner)
                key = _resolve_lock_expr(
                    item.context_expr, class_name, model, via
                )
                if key is not None:
                    facts.acquisitions.append((key, node, inner))
                    inner = inner + (key,)
            for stmt in node.body:
                visit(stmt, inner)
            return
        note(node, held)
        for child in ast.iter_child_nodes(node):
            visit(child, held)

    for stmt in func_node.body:
        visit(stmt, ())
    return facts


# ---------------------------------------------------------------------------
# Exemption comments
# ---------------------------------------------------------------------------


@dataclass
class Exemption:
    lock: str
    reason: Optional[str]
    line: int


def method_exemptions(
    module: ParsedModule, func_node: ast.AST
) -> List[Exemption]:
    """``# caller-holds-lock: <Lock.key> (reason)`` on the ``def`` line
    or the line immediately above it."""
    lines = module.source.splitlines()
    out: List[Exemption] = []
    for lineno in (func_node.lineno - 1, func_node.lineno):
        if 1 <= lineno <= len(lines):
            match = _EXEMPT_RE.search(lines[lineno - 1])
            if match:
                reason = match.group("reason")
                out.append(
                    Exemption(
                        lock=match.group("lock"),
                        reason=reason.strip() if reason else None,
                        line=lineno,
                    )
                )
    return out


# ---------------------------------------------------------------------------
# Project index: call graph, entry points, transitive locks
# ---------------------------------------------------------------------------

FKey = Tuple[str, str]  # (module.rel, qualname)


@dataclass
class FuncEntry:
    module: ParsedModule
    qualname: str
    node: ast.AST
    is_async: bool
    class_name: str
    facts: LockFacts
    _held_map: Optional[Dict[int, Tuple[str, ...]]] = None

    def held_by_node(self) -> Dict[int, Tuple[str, ...]]:
        """id(node) -> registered locks lexically held at that node."""
        if self._held_map is None:
            self._held_map = {
                id(node): held for node, held in self.facts.nodes
            }
        return self._held_map


class ProjectIndex:
    def __init__(
        self, modules: Sequence[ParsedModule], model: LockModel
    ) -> None:
        self.modules = list(modules)
        self.model = model
        self.via = model.via()
        self.funcs: Dict[FKey, FuncEntry] = {}
        self.methods_by_name: Dict[str, List[FKey]] = {}
        self.module_funcs_by_name: Dict[str, List[FKey]] = {}
        self.class_methods: Dict[Tuple[str, str], Dict[str, FKey]] = {}
        for module in modules:
            in_graph = not module.rel.endswith(_OUT_OF_GRAPH_SUFFIXES)
            for cls in module.classes():
                table: Dict[str, FKey] = {}
                for m in cls.methods:
                    fkey = (module.rel, m.qualname)
                    table[m.node.name] = fkey
                    if in_graph:
                        self.methods_by_name.setdefault(
                            m.node.name, []
                        ).append(fkey)
                self.class_methods[(module.rel, cls.name)] = table
            for fn in module.functions():
                fkey = (module.rel, fn.qualname)
                self.funcs[fkey] = FuncEntry(
                    module=module,
                    qualname=fn.qualname,
                    node=fn.node,
                    is_async=fn.is_async,
                    class_name=fn.class_name,
                    facts=lock_facts(
                        fn.node, fn.class_name, model, self.via
                    ),
                )
                if (
                    in_graph
                    and fn.class_name == ""
                    and "." not in fn.qualname
                ):
                    self.module_funcs_by_name.setdefault(
                        fn.qualname, []
                    ).append(fkey)
        self.call_edges: Dict[FKey, Set[FKey]] = {}
        self.entry_sets: Dict[FKey, Set[str]] = {
            k: set() for k in self.funcs
        }
        self._build_graph()
        self.direct_locks: Dict[FKey, Set[str]] = {
            k: {key for key, _, _ in e.facts.acquisitions}
            for k, e in self.funcs.items()
        }
        self.trans_locks = self._closure(self.direct_locks)
        self.direct_blocking: Dict[FKey, Optional[str]] = {
            k: _first_blocking(e.node) for k, e in self.funcs.items()
        }
        self._propagate_entries()

    # -- resolution ---------------------------------------------------------

    def _local_aliases(
        self, fkey: FKey, entry: FuncEntry
    ) -> Dict[str, Set[FKey]]:
        """``fn = self._dispatch_embed`` / ``fn = getattr(self,
        "_dispatch_" + kind)`` local single-name aliases."""
        aliases: Dict[str, Set[FKey]] = {}
        table = self.class_methods.get(
            (entry.module.rel, entry.class_name), {}
        )
        for node in body_nodes(entry.node):
            if not isinstance(node, ast.Assign):
                continue
            if len(node.targets) != 1 or not isinstance(
                node.targets[0], ast.Name
            ):
                continue
            name = node.targets[0].id
            targets = self._ref_targets(node.value, entry, table)
            if targets:
                aliases.setdefault(name, set()).update(targets)
        return aliases

    def _prefix_methods(
        self, table: Dict[str, FKey], prefix: str
    ) -> Set[FKey]:
        return {
            fkey
            for mname, fkey in table.items()
            if mname.startswith(prefix)
        }

    def _ref_targets(
        self,
        expr: ast.AST,
        entry: FuncEntry,
        table: Dict[str, FKey],
    ) -> Set[FKey]:
        """A callable *reference* (not a call) -> candidate functions:
        ``self.m``, ``x.m``, a bare name, ``functools.partial(f, ...)``
        or ``getattr(self, "prefix" + dynamic)``."""
        if isinstance(expr, ast.Attribute):
            if (
                isinstance(expr.value, ast.Name)
                and expr.value.id == "self"
                and expr.attr in table
            ):
                return {table[expr.attr]}
            if expr.attr in _GENERIC_ATTRS:
                return set()
            out = set(self.methods_by_name.get(expr.attr, ()))
            out.update(self.module_funcs_by_name.get(expr.attr, ()))
            return out
        if isinstance(expr, ast.Name):
            hits = self.module_funcs_by_name.get(expr.id, ())
            same = {k for k in hits if k[0] == entry.module.rel}
            return same or set(hits)
        if isinstance(expr, ast.Call):
            func = expr.func
            fname = (
                func.id
                if isinstance(func, ast.Name)
                else func.attr if isinstance(func, ast.Attribute) else None
            )
            if fname == "partial" and expr.args:
                return self._ref_targets(expr.args[0], entry, table)
            if fname == "getattr" and len(expr.args) >= 2:
                prefix = _literal_prefix(expr.args[1])
                if prefix is not None:
                    return self._prefix_methods(table, prefix)
        return set()

    def _call_targets(
        self,
        call: ast.Call,
        entry: FuncEntry,
        table: Dict[str, FKey],
        aliases: Dict[str, Set[FKey]],
    ) -> Set[FKey]:
        func = call.func
        if isinstance(func, ast.Name):
            if func.id in aliases:
                return aliases[func.id]
            hits = self.module_funcs_by_name.get(func.id, ())
            same = {k for k in hits if k[0] == entry.module.rel}
            return same or set(hits)
        if isinstance(func, ast.Attribute):
            if (
                isinstance(func.value, ast.Name)
                and func.value.id == "self"
                and func.attr in table
            ):
                return {table[func.attr]}
            if func.attr in _GENERIC_ATTRS:
                return set()
            out = set(self.methods_by_name.get(func.attr, ()))
            out.update(self.module_funcs_by_name.get(func.attr, ()))
            return out
        if isinstance(func, ast.Call):  # getattr(self, "...")(args)
            return self._ref_targets(func, entry, table)
        return set()

    # -- graph construction -------------------------------------------------

    def _build_graph(self) -> None:
        self.call_sites: Dict[FKey, List[Tuple[FKey, ast.Call]]] = {}
        for fkey, entry in self.funcs.items():
            table = self.class_methods.get(
                (entry.module.rel, entry.class_name), {}
            )
            aliases = self._local_aliases(fkey, entry)
            edges: Set[FKey] = set()
            for node in body_nodes(entry.node):
                if not isinstance(node, ast.Call):
                    continue
                targets = self._call_targets(node, entry, table, aliases)
                for target in targets:
                    self.call_sites.setdefault(target, []).append(
                        (fkey, node)
                    )
                edges |= targets
                self._note_entry_roots(node, entry, table, aliases)
            edges.discard(fkey)
            self.call_edges[fkey] = edges
            if entry.is_async:
                self.entry_sets[fkey].add("loop")

    def _note_entry_roots(
        self,
        call: ast.Call,
        entry: FuncEntry,
        table: Dict[str, FKey],
        aliases: Dict[str, Set[FKey]],
    ) -> None:
        func = call.func
        fname = (
            func.id
            if isinstance(func, ast.Name)
            else func.attr if isinstance(func, ast.Attribute) else None
        )
        ref = None
        category = None
        if fname == "Thread":
            for kw in call.keywords:
                if kw.arg == "target":
                    ref, category = kw.value, "thread"
        elif fname == "submit" and call.args:
            ref, category = call.args[0], "executor"
        elif fname == "run_in_executor" and len(call.args) >= 2:
            ref, category = call.args[1], "executor"
        if ref is None:
            return
        if isinstance(ref, ast.Name) and ref.id in aliases:
            targets = aliases[ref.id]
        else:
            targets = self._ref_targets(ref, entry, table)
        for fkey in targets:
            self.entry_sets[fkey].add(f"{category}:{fkey[1]}")

    def _closure(
        self, direct: Dict[FKey, Set[str]]
    ) -> Dict[FKey, Set[str]]:
        """Caller inherits every lock its callees (transitively)
        acquire — fixpoint over the call graph."""
        trans = {k: set(v) for k, v in direct.items()}
        changed = True
        while changed:
            changed = False
            for fkey, callees in self.call_edges.items():
                mine = trans[fkey]
                before = len(mine)
                for callee in callees:
                    mine |= trans.get(callee, set())
                changed = changed or len(mine) != before
        return trans

    def _propagate_entries(self) -> None:
        changed = True
        while changed:
            changed = False
            for fkey, callees in self.call_edges.items():
                src = self.entry_sets[fkey]
                if not src:
                    continue
                for callee in callees:
                    dst = self.entry_sets.get(callee)
                    if dst is None or src <= dst:
                        continue
                    dst |= src
                    changed = True

    # -- queries ------------------------------------------------------------

    def entry_weight(self, fkey: FKey) -> int:
        """Distinct thread-entry weight reaching this function: each
        Thread target and the loop count 1; an executor root counts 2
        (every pool in the package has >= 2 workers, so one root is
        already concurrent with itself)."""
        return sum(
            2 if entry.startswith("executor:") else 1
            for entry in self.entry_sets.get(fkey, ())
        )


def _literal_prefix(node: ast.AST) -> Optional[str]:
    """``"_dispatch_" + kind`` / f-string / constant -> literal prefix."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        return _literal_prefix(node.left)
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(
            head.value, str
        ):
            return head.value
    return None


# blocking-call classification for LWC016 (extends LWC006/013 to
# held-lock context): device readiness waits and upstream HTTP
_BLOCKING_NAMES = {"wait_device_ready", "block_until_ready"}
_HTTP_DOTTED = {
    "requests.get",
    "requests.post",
    "requests.put",
    "requests.delete",
    "requests.request",
    "requests.head",
    "urllib.request.urlopen",
}


def blocking_call(node: ast.AST) -> Optional[str]:
    """Human-readable description if ``node`` is a blocking operation
    LWC016 forbids under a held threading lock; None otherwise."""
    if isinstance(node, ast.Await):
        return "an `await`"
    if not isinstance(node, ast.Call):
        return None
    func = node.func
    if isinstance(func, ast.Name) and func.id in _BLOCKING_NAMES:
        return f"`{func.id}(...)`"
    if isinstance(func, ast.Attribute):
        if func.attr in _BLOCKING_NAMES:
            return f"`.{func.attr}(...)`"
        try:
            dotted = ast.unparse(func)
        except Exception:
            dotted = ""
        for known in _HTTP_DOTTED:
            if dotted == known or dotted.endswith("." + known):
                return f"upstream HTTP call `{dotted}(...)`"
    return None


def _first_blocking(func_node: ast.AST) -> Optional[str]:
    for node in body_nodes(func_node):
        desc = blocking_call(node)
        if desc is not None:
            return desc
    return None


# index cache: the three rules each call project() over the same parsed
# set within one run_lint; build the (call graph + closures) once.
# Keyed by object ids — valid because run_lint holds the modules alive
# across its project-rule loop.
_INDEX_CACHE: Dict[tuple, ProjectIndex] = {}


def project_index(
    modules: Sequence[ParsedModule],
) -> Optional[ProjectIndex]:
    model = load_model(modules)
    if model is None:
        return None
    cache_key = tuple(id(m) for m in modules)
    idx = _INDEX_CACHE.get(cache_key)
    if idx is None:
        idx = ProjectIndex(modules, model)
        _INDEX_CACHE.clear()
        _INDEX_CACHE[cache_key] = idx
    return idx
