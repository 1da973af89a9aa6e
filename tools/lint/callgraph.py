"""Module-qualified call graph over the linted program.

The interprocedural passes (taint, effects, the transitive-sort sweep)
all need the same three things the per-file rules cannot see: *who calls
whom* across module boundaries, *which import alias means which module*,
and *which attribute call can land on which method*.  This module builds
that once per lint run (shared through ``Program.callgraph()``) from the
already-parsed :class:`FileUnit` list — no re-parsing.

Resolution policy (deliberately conservative, documented here because
the passes inherit its precision):

* **Bare names** (``f(...)``) resolve to a top-level def or class in the
  *same file*, else through a ``from M import f`` alias; never by global
  name union — a bare ``benchmark()`` in sim code must not link to an
  unrelated ``benchmark`` in jax-side code.
* **``self.m(...)`` / ``cls.m(...)``** resolves to the enclosing class's
  method, walking program-visible base classes; if the class doesn't
  define it anywhere visible, it falls back to the union of all methods
  named ``m`` (the U401-style whole-program convention).
* **Module-alias chains** (``lsm.merge_delta_runs(...)``, ``t.time(...)``)
  expand through the import-alias table.  In-program targets become
  edges; the rest are recorded verbatim as *external chains* so sink
  predicates (``time.*``, ``numpy.random.*``) can match them even
  through ``import time as t``.
* **Other attribute calls** (``store.items(...)``) union over every
  method with that terminal name — over-approximate by design: taint
  must not miss an edge because the receiver's type is unknown.

Calls at module level are attributed to a synthetic ``<module>``
function per file, so import-time nondeterminism is reachable too.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field

from tools.lint.core import FileUnit, dotted

MODULE_BODY = "<module>"


def module_name(relpath: str) -> str:
    """``src/repro/state/lsm.py`` -> ``repro.state.lsm`` (the name the
    import system sees, so alias chains resolve against it)."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    if p.startswith("src/"):
        p = p[len("src/"):]
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


@dataclass
class FuncNode:
    """One function/method (or the synthetic module body) in the graph."""
    fid: str                 # "relpath::qualname"
    relpath: str
    qualname: str            # "Class.method", "func", "func.inner", "<module>"
    name: str                # terminal name
    cls: str | None          # enclosing class name, if a method
    node: ast.AST | None     # None for the synthetic module body
    lineno: int = 0

    @property
    def label(self) -> str:
        return f"{module_name(self.relpath)}:{self.qualname}"


@dataclass
class CallSite:
    """One call expression, attributed to its innermost enclosing def."""
    caller: str                        # caller fid
    call: ast.Call
    targets: tuple[str, ...] = ()      # resolved in-program callee fids
    external: tuple[str, ...] = ()     # expanded dotted chain if unresolved


@dataclass
class CallGraph:
    nodes: dict[str, FuncNode] = field(default_factory=dict)
    sites: list[CallSite] = field(default_factory=list)
    edges: dict[str, set[str]] = field(default_factory=dict)
    redges: dict[str, set[str]] = field(default_factory=dict)
    sites_by_caller: dict[str, list[CallSite]] = field(default_factory=dict)
    unit_of: dict[str, FileUnit] = field(default_factory=dict)  # fid -> unit
    methods_by_name: dict[str, list[str]] = field(default_factory=dict)

    def funcs_in(self, relpath: str) -> list[FuncNode]:
        return [n for n in self.nodes.values() if n.relpath == relpath]

    def forward_closure(self, roots: set[str]) -> set[str]:
        seen, todo = set(roots), list(roots)
        while todo:
            f = todo.pop()
            for g in self.edges.get(f, ()):
                if g not in seen:
                    seen.add(g)
                    todo.append(g)
        return seen

    def reverse_closure(self, roots: set[str]
                        ) -> tuple[set[str], dict[str, str]]:
        """Everything that can reach ``roots``, plus a parent map:
        ``parent[f]`` is the callee through which f first reached the
        root set (for rendering f -> ... -> root chains)."""
        seen, parent = set(roots), {}
        todo = sorted(roots)             # deterministic BFS order
        while todo:
            nxt: list[str] = []
            for f in todo:
                for g in sorted(self.redges.get(f, ())):
                    if g not in seen:
                        seen.add(g)
                        parent[g] = f
                        nxt.append(g)
            todo = nxt
        return seen, parent

    def chain(self, fid: str, parent: dict[str, str],
              stop: set[str]) -> list[str]:
        """Human-readable qualname chain from ``fid`` down to the first
        node inside ``stop`` (the root/sink set)."""
        out, cur, guard = [], fid, 0
        while cur is not None and guard < 32:
            out.append(self.nodes[cur].label if cur in self.nodes else cur)
            if cur in stop:
                break
            cur = parent.get(cur)
            guard += 1
        return out


class _Collector:
    """Per-unit def/class/import collection + call attribution."""

    def __init__(self, unit: FileUnit) -> None:
        self.unit = unit
        self.relpath = unit.relpath
        self.funcs: list[FuncNode] = []
        self.toplevel: dict[str, str] = {}          # name -> fid
        self.classes: dict[str, dict[str, str]] = {}  # cls -> {meth: fid}
        self.class_bases: dict[str, tuple[str, ...]] = {}
        self.aliases: dict[str, str] = {}           # bound name -> dotted
        self.calls: list[tuple[str, str | None, ast.Call]] = []
        # ^ (caller fid, enclosing class, call node)
        self.nested_edges: list[tuple[str, str]] = []

    def fid(self, qualname: str) -> str:
        return f"{self.relpath}::{qualname}"

    def collect(self) -> None:
        mod = FuncNode(self.fid(MODULE_BODY), self.relpath, MODULE_BODY,
                       MODULE_BODY, None, None)
        self.funcs.append(mod)
        self._imports()
        self._walk_body(self.unit.tree.body, [], None, mod.fid)

    def _imports(self) -> None:
        pkg = module_name(self.relpath).split(".")
        for node in ast.walk(self.unit.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    bound = a.asname or a.name.split(".")[0]
                    target = a.name if a.asname else a.name.split(".")[0]
                    self.aliases[bound] = target
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = pkg[: len(pkg) - node.level]
                    if node.module:
                        base = base + node.module.split(".")
                elif node.module:
                    base = node.module.split(".")
                else:
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    bound = a.asname or a.name
                    self.aliases[bound] = ".".join(base + [a.name])

    def _walk_body(self, body: list[ast.stmt], quals: list[str],
                   cls: str | None, owner: str) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = quals + [stmt.name]
                fn = FuncNode(self.fid(".".join(q)), self.relpath,
                              ".".join(q), stmt.name, cls, stmt, stmt.lineno)
                self.funcs.append(fn)
                if cls is not None and len(quals) == 1:
                    self.classes.setdefault(cls, {})[stmt.name] = fn.fid
                elif not quals:
                    self.toplevel[stmt.name] = fn.fid
                # a nested def is conservatively reachable from its encloser
                if quals:
                    self.nested_edges.append((owner, fn.fid))
                for dec in stmt.decorator_list:
                    self._calls_in(dec, owner, cls)
                self._walk_body(stmt.body, q, cls, fn.fid)
            elif isinstance(stmt, ast.ClassDef):
                if not quals:
                    self.classes.setdefault(stmt.name, {})
                    self.class_bases[stmt.name] = tuple(
                        b for b in (self._base_name(x) for x in stmt.bases)
                        if b)
                for dec in stmt.decorator_list:
                    self._calls_in(dec, owner, cls)
                self._walk_body(stmt.body, quals + [stmt.name],
                                stmt.name if not quals else cls, owner)
            else:
                self._calls_in(stmt, owner, cls)

    @staticmethod
    def _base_name(node: ast.AST) -> str | None:
        chain = dotted(node)
        return chain[-1] if chain else None

    def _calls_in(self, node: ast.AST, owner: str, cls: str | None) -> None:
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                self.calls.append((owner, cls, n))


def build_callgraph(units: list[FileUnit]) -> CallGraph:
    cg = CallGraph()
    collectors = [_Collector(u) for u in units]
    for c in collectors:
        c.collect()

    toplevel: dict[tuple[str, str], str] = {}
    classes: dict[tuple[str, str], dict[str, str]] = {}
    classes_by_name: dict[str, list[tuple[str, str]]] = {}
    bases: dict[tuple[str, str], tuple[str, ...]] = {}
    module_index: dict[str, str] = {}
    for c in collectors:
        module_index[module_name(c.relpath)] = c.relpath
        for name, fid in c.toplevel.items():
            toplevel[(c.relpath, name)] = fid
        for cls, meths in c.classes.items():
            classes[(c.relpath, cls)] = meths
            classes_by_name.setdefault(cls, []).append((c.relpath, cls))
            bases[(c.relpath, cls)] = c.class_bases.get(cls, ())
        for fn in c.funcs:
            cg.nodes[fn.fid] = fn
            cg.unit_of[fn.fid] = c.unit
            if fn.cls is not None:
                cg.methods_by_name.setdefault(fn.name, []).append(fn.fid)

    def class_method(relpath: str, cls: str, name: str,
                     depth: int = 0) -> str | None:
        meths = classes.get((relpath, cls))
        if meths and name in meths:
            return meths[name]
        if depth >= 4:
            return None
        for base in bases.get((relpath, cls), ()):
            for (rp2, cls2) in classes_by_name.get(base, ()):
                hit = class_method(rp2, cls2, name, depth + 1)
                if hit:
                    return hit
        return None

    def ctor(relpath: str, cls_name: str) -> tuple[str, ...]:
        hit = class_method(relpath, cls_name, "__init__")
        return (hit,) if hit else ()

    def resolve(c: _Collector, cls: str | None,
                call: ast.Call) -> tuple[tuple[str, ...], tuple[str, ...]]:
        chain = dotted(call.func)
        if not chain:
            return (), ()
        if len(chain) == 1:
            name = chain[0]
            if (c.relpath, name) in toplevel:
                return (toplevel[(c.relpath, name)],), ()
            if (c.relpath, name) in classes:
                return ctor(c.relpath, name), ()
            if name in c.aliases:
                return _resolve_dotted(c.aliases[name].split("."))
            return (), (name,)
        if chain[0] in ("self", "cls") and cls is not None:
            hit = class_method(c.relpath, cls, chain[-1])
            if hit and len(chain) == 2:
                return (hit,), ()
            return tuple(cg.methods_by_name.get(chain[-1], ())), ()
        if chain[0] in c.aliases:
            full = c.aliases[chain[0]].split(".") + list(chain[1:])
            return _resolve_dotted(full)
        # unknown receiver: union over same-named methods (U401-style)
        return tuple(cg.methods_by_name.get(chain[-1], ())), ()

    def _resolve_dotted(full: list[str]
                        ) -> tuple[tuple[str, ...], tuple[str, ...]]:
        for i in range(len(full) - 1, 0, -1):
            mod = ".".join(full[:i])
            if mod not in module_index:
                continue
            rel2, rest = module_index[mod], full[i:]
            if len(rest) == 1:
                if (rel2, rest[0]) in toplevel:
                    return (toplevel[(rel2, rest[0])],), ()
                if (rel2, rest[0]) in classes:
                    return ctor(rel2, rest[0]), ()
            elif len(rest) == 2 and (rel2, rest[0]) in classes:
                hit = class_method(rel2, rest[0], rest[1])
                if hit:
                    return (hit,), ()
            return (), ()        # known module, unknown member: no edge
        return (), tuple(full)   # fully external: keep chain for sinks

    for c in collectors:
        for owner, cls, call in c.calls:
            targets, external = resolve(c, cls, call)
            site = CallSite(owner, call, targets, external)
            cg.sites.append(site)
            cg.sites_by_caller.setdefault(owner, []).append(site)
            for t in targets:
                cg.edges.setdefault(owner, set()).add(t)
                cg.redges.setdefault(t, set()).add(owner)
        for owner, nested in c.nested_edges:
            cg.edges.setdefault(owner, set()).add(nested)
            cg.redges.setdefault(nested, set()).add(owner)
    return cg
