"""Interprocedural nondeterminism passes.

**T501 — nondeterminism reachability.**  R305 bans ``random``/``time``/
``datetime``/``uuid``/``secrets`` *imports* in the 11 golden-trace-critical
modules; nothing stopped a golden function from calling a helper in a
non-golden module that reads the wall clock.  T501 closes that hole with
a call-graph proof: every function that (transitively) calls a
nondeterminism sink is *tainted*, and every call edge whose caller lives
in a golden module and whose callee is tainted is a finding — reported
at the call site, with the reconstructed path down to the sink.  Direct
sink calls are deliberately NOT re-reported here: those are D101/D102's
(and R305's) per-file job; T501 owns the edges the per-file rules cannot
see.

**T502 — transitive non-stable sort.**  D103 polices ``np.argsort``
without ``kind="stable"`` inside sim-scope files; a sim function calling
into a jax-side helper (models/, kernels/, ...) that sorts unstably
escapes it.  T502 sweeps call sites in sim-scope functions whose callee
chain — through *non-sim* files only, so D103 keeps sole ownership of
its scope — reaches a non-stable ``argsort``.

Sink definitions mirror D101/D102 exactly (unseeded RNG constructors and
global-state RNG calls; whole wall-clock-ish modules), but match the
*alias-expanded* chain, so ``import time as t; t.time()`` is still a
sink.  Fixture convention: paths outside ``src/repro/`` count as golden
AND sim AND non-sim at once — the same full-panel convention the
per-file rules use, which lets a single fixture file exercise an
inherently cross-file property.

**The observability carve-out.**  The obs layer (``src/repro/obs/``) may
read ``time.perf_counter`` for its registry ``Timer``, the one clock
read left there (the profiler-clock spans of ``obs/spans.py`` read
none).  That is a *write-only*
side channel: a golden function calling ``self.tracer.record(...)`` as a
bare statement throws the result away, so no clock value can flow back
into a decision.  T501 therefore refuses to propagate taint across a
call site when (a) every tainted target lives under ``src/repro/obs/``
AND (b) the call's value is discarded (the call is the whole of an
``ast.Expr`` statement).  This is scoped at the *propagation* level, not
a blanket module exemption: an obs value that IS captured
(``x = tracer.record(...)``, ``if registry.timer(...)``) still taints the
caller and is reported — the proof obligation stays "no obs value
reaches a golden decision", checked per edge.
"""
from __future__ import annotations

import ast

from tools.lint.core import FileUnit, Finding, Rule, dotted, get_callgraph, \
    register_rule
from tools.lint.callgraph import CallGraph, CallSite
from tools.lint.rules import GOLDEN_MODULES, SIM_SCOPE, UnseededRandom

_CLOCKISH = ("time", "datetime", "uuid", "secrets")


def _fixture(relpath: str) -> bool:
    return not relpath.startswith("src/repro/")


def _golden(relpath: str) -> bool:
    return _fixture(relpath) or relpath in GOLDEN_MODULES


def _sim(relpath: str) -> bool:
    return _fixture(relpath) or relpath.startswith(SIM_SCOPE)


def _non_sim(relpath: str) -> bool:
    return _fixture(relpath) or not relpath.startswith(SIM_SCOPE)


OBS_SCOPE = "src/repro/obs/"


def _obs(relpath: str) -> bool:
    """Is this file part of the write-only observability layer?  NOT
    fixture-widened: the carve-out must only ever apply to the real obs
    package (a test fixture opts in by using an ``src/repro/obs/``
    pretend path)."""
    return relpath.startswith(OBS_SCOPE)


def _discarded(cg: CallGraph, site: CallSite) -> bool:
    """True when the call's value is thrown away — the call expression is
    the whole of an ``ast.Expr`` statement in its caller's body (module
    body for the synthetic ``<module>`` function)."""
    fn = cg.nodes[site.caller].node
    tree = fn if fn is not None else cg.unit_of[site.caller].tree
    for node in ast.walk(tree):
        if isinstance(node, ast.Expr) and node.value is site.call:
            return True
    return False


def _obs_exempt(cg: CallGraph, site: CallSite, bad: list[str]) -> bool:
    """The observability carve-out (module docstring): a *discarded* call
    whose every tainted target lives in the obs layer cannot feed a clock
    value back into a decision, so taint must not cross this edge."""
    return all(_obs(cg.nodes[t].relpath) for t in bad) \
        and _discarded(cg, site)


def sink_label(site: CallSite) -> str | None:
    """The sink this external call hits, or None.  Mirrors D101/D102 on
    the alias-expanded chain."""
    ch = site.external
    if not ch:
        return None
    if ch[0] in _CLOCKISH:
        return ".".join(ch)
    if ch[0] == "random" and len(ch) >= 2:
        if ch[1] in UnseededRandom._RANDOM_FNS:
            return ".".join(ch)
        if ch[1] == "Random" and not site.call.args and not site.call.keywords:
            return "random.Random"        # unseeded
    if ch[0] == "numpy" and len(ch) >= 2 and ch[1] == "random":
        if ch[-1] == "default_rng" and not site.call.args \
                and not site.call.keywords:
            return "numpy.random.default_rng"   # unseeded
        if len(ch) == 3 and ch[2] in UnseededRandom._NP_GLOBAL_FNS:
            return ".".join(ch)
    return None


def _sink_chain(cg: CallGraph, fid: str, parent: dict[str, str],
                direct: dict[str, str]) -> tuple[list[str], str]:
    """(qualname path from fid to the sinking function, sink name)."""
    names, cur = [], fid
    for _ in range(32):
        names.append(cg.nodes[cur].label)
        if cur in direct:
            return names, direct[cur]
        cur = parent[cur]
    return names, "?"


@register_rule
class TaintReachability(Rule):
    """Golden-module call edge reaching a nondeterminism sink."""
    id = "T501"
    title = "golden-module call transitively reaches a nondeterminism sink"

    def __init__(self) -> None:
        self._by_path: dict[str, list[Finding]] = {}

    def prepare(self, units: list[FileUnit]) -> None:
        self._by_path = {}
        cg = get_callgraph(units)
        direct: dict[str, str] = {}
        for site in cg.sites:
            lbl = sink_label(site)
            if lbl is not None and site.caller not in direct:
                direct[site.caller] = lbl
        if not direct:
            return
        # site-level taint fixpoint rather than cg.reverse_closure:
        # propagation must be able to REFUSE an edge (the obs carve-out
        # needs the call *expression*, which the fid-level reverse graph
        # has already erased).  Nested-def containment edges have no call
        # site, so they propagate unconditionally, as before.
        site_pairs = {(s.caller, t) for s in cg.sites for t in s.targets}
        nested = sorted((o, t) for o, ts in cg.edges.items()
                        for t in ts if (o, t) not in site_pairs)
        tainted, parent = set(direct), {}
        changed = True
        while changed:
            changed = False
            for site in cg.sites:
                if site.caller in tainted:
                    continue
                bad = sorted(t for t in site.targets if t in tainted)
                if not bad or _obs_exempt(cg, site, bad):
                    continue
                tainted.add(site.caller)
                parent[site.caller] = bad[0]
                changed = True
            for o, t in nested:
                if o not in tainted and t in tainted:
                    tainted.add(o)
                    parent[o] = t
                    changed = True
        for site in cg.sites:
            caller = cg.nodes[site.caller]
            if not _golden(caller.relpath):
                continue
            bad = sorted(t for t in site.targets if t in tainted)
            if not bad or _obs_exempt(cg, site, bad):
                continue
            chain, sink = _sink_chain(cg, bad[0], parent, direct)
            unit = cg.unit_of[site.caller]
            self._by_path.setdefault(unit.relpath, []).append(unit.finding(
                self, site.call,
                f"call reaches nondeterminism sink {sink}(...) via "
                f"{' -> '.join(chain)} — golden-trace-critical modules "
                f"must be pure functions of (seed, inputs); thread an "
                f"explicit seed/engine.now through the callee instead"))

    def visit(self, unit: FileUnit) -> list[Finding]:
        return list(self._by_path.get(unit.relpath, ()))


@register_rule
class TransitiveUnstableSort(Rule):
    """Sim-scope call whose callee chain performs a non-stable argsort
    outside D103's per-file scope."""
    id = "T502"
    title = "sim-scope call reaches a non-stable argsort in jax-side code"

    _STABLE_KINDS = {"stable", "mergesort"}

    def __init__(self) -> None:
        self._by_path: dict[str, list[Finding]] = {}

    def _has_unstable_sort(self, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            chain = dotted(node.func)
            if not chain or chain[-1] != "argsort":
                continue
            kind = next((kw.value for kw in node.keywords
                         if kw.arg == "kind"), None)
            if kind is None or not (isinstance(kind, ast.Constant)
                                    and kind.value in self._STABLE_KINDS):
                return True
        return False

    def prepare(self, units: list[FileUnit]) -> None:
        self._by_path = {}
        cg = get_callgraph(units)
        roots = {fid for fid, fn in cg.nodes.items()
                 if fn.node is not None and _non_sim(fn.relpath)
                 and self._has_unstable_sort(fn.node)}
        if not roots:
            return
        # close the taint through NON-sim files only: a sim-file
        # intermediary gets its own finding at ITS outbound call, and
        # D103 keeps sole ownership of sorts inside sim files
        tainted, parent = set(roots), {}
        frontier = sorted(roots)
        while frontier:
            nxt: list[str] = []
            for f in frontier:
                for g in sorted(cg.redges.get(f, ())):
                    if g in tainted:
                        continue
                    tainted.add(g)
                    parent[g] = f
                    if _non_sim(cg.nodes[g].relpath):
                        nxt.append(g)
            frontier = nxt
        for site in cg.sites:
            caller = cg.nodes[site.caller]
            if not _sim(caller.relpath):
                continue
            bad = sorted(t for t in site.targets
                         if t in tainted and _non_sim(cg.nodes[t].relpath))
            if not bad:
                continue
            chain = cg.chain(bad[0], parent, roots)
            unit = cg.unit_of[site.caller]
            self._by_path.setdefault(unit.relpath, []).append(unit.finding(
                self, site.call,
                f"call reaches a non-stable argsort via "
                f"{' -> '.join(chain)} — tie order there depends on the "
                f"sort algorithm; ordering-sensitive sim logic must rank "
                f"ties deterministically (kind=\"stable\")"))

    def visit(self, unit: FileUnit) -> list[Finding]:
        return list(self._by_path.get(unit.relpath, ()))
