"""The reprolint rule panel: repo-specific invariants as AST checks.

Four families (docs/static-analysis.md has the user-facing table):

* **D — determinism.**  Simulation, policy, scenario, state and
  arbitration code must be a pure function of (seed, inputs): no unseeded
  RNGs, no wall clock, no unstable sorts deciding order among ties, no
  iteration over sets feeding ordering-sensitive logic.  These are the
  invariants behind the byte-identical golden traces and the
  scalar-vs-vectorized fleet oracle.
* **F — float accounting.**  Resource footprints (MB) are accumulated
  floats; comparing them bare reproduces the ``Cluster.fits`` phantom-
  denial bug PR 6 fixed.  All MB comparisons go through the blessed
  epsilon helpers (``repro.core.units``) or carry an explicit ``_EPS``
  term; O(1)-incremental budget counters must be audited in the function
  that mutates them.
* **R — registry discipline.**  Policies are constructed through
  ``@register_policy``/``make_policy`` (never ``cfg.policy`` string
  dispatch), every ``ScalingPolicy`` subclass is registered, and
  ``HistoryRow``\\ s are immutable once appended except in the two
  blessed driver modules.  Golden-trace-critical modules
  import no nondeterminism sources at all.
* **U — units.**  MB, CPU slots and seconds must not cross call
  boundaries: a parameter named ``*_mb`` fed an argument named ``*_s``
  (or ``slots``/``cores``) is flagged, using parameter-name conventions
  collected from the linted tree itself.

Every rule has a known-bad and known-good fixture under
``tools/lint/fixtures/`` (the CI self-check and ``tests/test_lint.py``
both run them).
"""
from __future__ import annotations

import ast
import re

from tools.lint.core import (FileUnit, Finding, Rule, dotted, identifiers,
                             register_rule, terminal_name)

# The default scope for determinism rules: everything that feeds the
# simulation's decision traces.  models/, kernels/, launch/, train/ and
# configs/ are jax-side code whose randomness is explicitly keyed and
# whose wall-clock use is benchmarking, not simulation.
SIM_SCOPE = ("src/repro/streaming/", "src/repro/core/",
             "src/repro/scenarios/", "src/repro/state/",
             "src/repro/migration/", "src/repro/serve/",
             "src/repro/data/")

# Accounting code where MB quantities are budget-compared.
ACCOUNTING_SCOPE = ("src/repro/core/", "src/repro/scenarios/",
                    "src/repro/migration/", "src/repro/serve/")

# Modules whose decisions the four golden traces pin byte-for-byte.
GOLDEN_MODULES = (
    "src/repro/streaming/engine.py",
    "src/repro/streaming/operators.py",
    "src/repro/streaming/events.py",
    "src/repro/streaming/graph.py",
    "src/repro/core/controller.py",
    "src/repro/core/policy.py",
    "src/repro/core/justin.py",
    "src/repro/core/ds2.py",
    "src/repro/core/placement.py",
    "src/repro/state/lsm.py",
    "src/repro/data/nexmark.py",
)

# Modules allowed to mutate HistoryRow after append: the controller owns
# the rows; the co-location drivers back-fill admission outcomes on the
# window that produced them.
HISTORY_OWNERS = ("src/repro/core/controller.py",
                  "src/repro/scenarios/cluster.py")


def _is_np_random(chain: tuple[str, ...]) -> bool:
    return len(chain) >= 2 and chain[0] in ("np", "numpy") \
        and chain[1] == "random"


# ---------------------------------------------------------------------------
# D — determinism
# ---------------------------------------------------------------------------

@register_rule
class UnseededRandom(Rule):
    """Unseeded or global-state RNG in simulation code."""
    id = "D101"
    title = "unseeded / global-state RNG in sim code"
    scope = SIM_SCOPE

    # stdlib `random` module-level functions share one global, implicitly
    # seeded generator; any use in sim code is a determinism leak
    _RANDOM_FNS = {"random", "randint", "randrange", "choice", "choices",
                   "shuffle", "sample", "uniform", "gauss", "normalvariate",
                   "betavariate", "expovariate", "seed", "getrandbits"}
    # numpy legacy global-state API (np.random.<fn> other than default_rng
    # and the Generator/Random types)
    _NP_GLOBAL_FNS = {"rand", "randn", "randint", "random", "random_sample",
                      "choice", "shuffle", "permutation", "seed", "uniform",
                      "normal", "lognormal", "poisson", "exponential"}

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = dotted(node.func)
            if not chain:
                continue
            if _is_np_random(chain) and chain[-1] == "default_rng" \
                    and not node.args and not node.keywords:
                out.append(unit.finding(
                    self, node,
                    "np.random.default_rng() without a seed — sim code "
                    "must derive every stream from an explicit seed"))
            elif _is_np_random(chain) and len(chain) == 3 \
                    and chain[-1] in self._NP_GLOBAL_FNS:
                out.append(unit.finding(
                    self, node,
                    f"numpy global-state RNG np.random.{chain[-1]}(...) — "
                    f"use a seeded np.random.default_rng(seed) generator"))
            elif chain == ("random", "Random") and not node.args \
                    and not node.keywords:
                out.append(unit.finding(
                    self, node,
                    "random.Random() without a seed — pass an explicit "
                    "seed"))
            elif len(chain) == 2 and chain[0] == "random" \
                    and chain[1] in self._RANDOM_FNS:
                out.append(unit.finding(
                    self, node,
                    f"stdlib global RNG random.{chain[1]}(...) — use a "
                    f"seeded random.Random(seed) instance"))
        return out


@register_rule
class WallClock(Rule):
    """Wall-clock reads inside engine/controller/scenario paths."""
    id = "D102"
    title = "wall clock in sim code"
    scope = SIM_SCOPE

    _BANNED = {("time", "time"), ("time", "time_ns"),
               ("time", "perf_counter"), ("time", "perf_counter_ns"),
               ("time", "monotonic"), ("time", "monotonic_ns"),
               ("datetime", "now"), ("datetime", "utcnow"),
               ("datetime", "today"), ("date", "today")}

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if isinstance(node, ast.Call):
                chain = dotted(node.func)
                if chain and chain[-2:] in self._BANNED:
                    out.append(unit.finding(
                        self, node,
                        f"wall-clock read {'.'.join(chain)}(...) in sim "
                        f"code — simulated time lives on the engine "
                        f"(engine.now); wall-clock belongs in benchmarks"))
        return out


@register_rule
class UnstableArgsort(Rule):
    """np.argsort without kind="stable" deciding order in sim code."""
    id = "D103"
    title = "non-stable argsort in arbitration/sim code"
    scope = SIM_SCOPE

    _STABLE_KINDS = {"stable", "mergesort"}

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = dotted(node.func)
            if not chain or chain[-1] != "argsort":
                continue
            kind = next((kw.value for kw in node.keywords
                         if kw.arg == "kind"), None)
            if kind is None:
                out.append(unit.finding(
                    self, node,
                    "argsort without kind=\"stable\" — tie order depends "
                    "on the sort algorithm (quicksort diverges from stable "
                    "order at >=17 tied elements); arbitration and "
                    "partitioning must rank ties deterministically"))
            elif not (isinstance(kind, ast.Constant)
                      and kind.value in self._STABLE_KINDS):
                out.append(unit.finding(
                    self, node,
                    "argsort with a non-stable kind= — use "
                    "kind=\"stable\""))
        return out


@register_rule
class SetIteration(Rule):
    """Iterating a set (or materializing one into a sequence) feeds
    ordering-sensitive logic with hash order."""
    id = "D104"
    title = "set iteration feeding ordering-sensitive logic"
    scope = SIM_SCOPE

    def _is_set_expr(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call):
            chain = dotted(node.func)
            return chain in (("set",), ("frozenset",))
        return False

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if isinstance(node, ast.For) and self._is_set_expr(node.iter):
                out.append(unit.finding(
                    self, node.iter,
                    "for-loop over a set: iteration order is hash order — "
                    "sort it (sorted(...)) or keep a dict/list"))
            elif isinstance(node, (ast.ListComp, ast.GeneratorExp)):
                for gen in node.generators:
                    if self._is_set_expr(gen.iter):
                        out.append(unit.finding(
                            self, gen.iter,
                            "comprehension over a set: iteration order is "
                            "hash order — sort it or keep a dict/list"))
            elif isinstance(node, ast.Call):
                chain = dotted(node.func)
                if chain in (("list",), ("tuple",), ("enumerate",)) \
                        and node.args and self._is_set_expr(node.args[0]):
                    out.append(unit.finding(
                        self, node,
                        f"{chain[0]}() over a set materializes hash order "
                        f"— use sorted(...)"))
        return out


# ---------------------------------------------------------------------------
# F — float accounting
# ---------------------------------------------------------------------------

_MEM_TOKEN = re.compile(r"(?:^|_)(?:mb|mem|memory|payload)(?:$|_)|_mb$")


def _memish(name: str) -> bool:
    return bool(_MEM_TOKEN.search(name.lower()))


def _side_is_memish(node: ast.AST) -> bool:
    return any(_memish(i) for i in identifiers(node))


def _is_zero_const(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) \
        and isinstance(node.value, (int, float)) and node.value == 0


@register_rule
class BareFloatComparison(Rule):
    """Bare ==/<=/</>=/> between MB footprints outside the blessed epsilon
    helpers — the Cluster.fits phantom-denial class (PR 6)."""
    id = "F201"
    title = "bare float comparison on MB footprints"
    scope = ACCOUNTING_SCOPE

    _CMP = (ast.Eq, ast.NotEq, ast.Lt, ast.LtE, ast.Gt, ast.GtE)

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if not isinstance(node, ast.Compare):
                continue
            if not any(isinstance(op, self._CMP) for op in node.ops):
                continue
            # blessed: an epsilon term anywhere in the comparison
            if any("eps" in i.lower() for i in identifiers(node)):
                continue
            sides = [node.left, *node.comparators]
            memish = [_side_is_memish(s) for s in sides]
            if not any(memish):
                continue
            # `x_mb > 0` / `x_mb == 0` emptiness checks are drift-safe
            if len(sides) == 2 and any(
                    _is_zero_const(s) for m, s in zip(memish, sides)
                    if not m):
                continue
            # int-typed sides (len(), counts) don't drift; skip when every
            # mem-ish side is wrapped in len()/int()
            if all(isinstance(s, ast.Call)
                   and dotted(s.func) in (("len",), ("int",))
                   for m, s in zip(memish, sides) if m):
                continue
            out.append(unit.finding(
                self, node,
                "bare comparison on an MB footprint — accumulated float "
                "attribution drifts; use repro.core.units (mem_fits/"
                "mem_exceeds/mem_close) or an explicit _EPS term"))
        return out


@register_rule
class UnauditedCounterUpdate(Rule):
    """In-place += / -= on an O(1)-incremental budget counter in a
    function with no audit (assert or _recount) — the invariant that
    keeps the incremental totals honest against the dict sums."""
    id = "F202"
    title = "unaudited in-place budget-counter update"
    scope = ("src/repro/core/", "src/repro/scenarios/")

    _COUNTER = re.compile(r"(?:^|_)(?:cpu|mem|slots)_(?:total|in_use)$")
    _AUDIT_CALLS = {"_recount", "refresh", "audit"}

    def _audited(self, fn: ast.AST) -> bool:
        for n in ast.walk(fn):
            if isinstance(n, ast.Assert):
                return True
            if isinstance(n, ast.Call):
                t = terminal_name(n.func)
                if t in self._AUDIT_CALLS:
                    return True
        return False

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for fn in ast.walk(unit.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            hits = [n for n in ast.walk(fn)
                    if isinstance(n, ast.AugAssign)
                    and isinstance(n.op, (ast.Add, ast.Sub))
                    and (t := terminal_name(n.target)) is not None
                    and self._COUNTER.search(t)]
            if hits and not self._audited(fn):
                for n in hits:
                    out.append(unit.finding(
                        self, n,
                        f"in-place update of budget counter "
                        f"'{terminal_name(n.target)}' in "
                        f"{fn.name}() with no audit — pair O(1) counter "
                        f"maintenance with an assert against the budget "
                        f"or a _recount()"))
        return out


# ---------------------------------------------------------------------------
# R — registry discipline
# ---------------------------------------------------------------------------

@register_rule
class PolicyStringDispatch(Rule):
    """String dispatch on a `.policy` attribute — the pre-PR-3 pattern the
    registry replaced (behavior forks silently for unregistered names)."""
    id = "R301"
    title = "string dispatch on cfg.policy"

    def _policy_attr(self, node: ast.AST) -> bool:
        return isinstance(node, ast.Attribute) and node.attr == "policy"

    def _str_const(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return True
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return all(self._str_const(e) for e in node.elts)
        return False

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left, *node.comparators]
            if any(self._policy_attr(s) for s in sides) \
                    and any(self._str_const(s) for s in sides):
                out.append(unit.finding(
                    self, node,
                    "string dispatch on a .policy name — construct the "
                    "policy via make_policy(...) and dispatch on the "
                    "instance (isinstance / protocol hooks), or register "
                    "a policy subclass"))
        return out


@register_rule
class UnregisteredPolicy(Rule):
    """A ScalingPolicy subclass without @register_policy is invisible to
    every --policy flag, the grid, and the co-location driver."""
    id = "R303"
    title = "ScalingPolicy subclass not registered"

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if not any(terminal_name(b) == "ScalingPolicy"
                       for b in node.bases):
                continue
            registered = False
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if terminal_name(target) == "register_policy":
                    registered = True
            if not registered:
                out.append(unit.finding(
                    self, node,
                    f"policy class {node.name} subclasses ScalingPolicy "
                    f"but is not @register_policy(...)-decorated — it is "
                    f"unreachable from every --policy flag and driver"))
        return out


@register_rule
class HistoryRowMutation(Rule):
    """Mutating HistoryRow fields after append outside the blessed owner
    modules — downstream SLO metrics treat histories as immutable."""
    id = "R304"
    title = "HistoryRow mutated after append"
    exempt = HISTORY_OWNERS

    def _history_subscript(self, node: ast.AST) -> bool:
        return isinstance(node, ast.Subscript) \
            and terminal_name(node.value) == "history"

    def _scan_block(self, unit: FileUnit, block: ast.AST,
                    out: list[Finding]) -> None:
        aliases: set[str] = set()
        for node in ast.walk(block):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not block:
                continue
            if isinstance(node, ast.Assign):
                # row = xxx.history[...]
                if self._history_subscript(node.value):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            aliases.add(t.id)
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            else:
                continue
            for t in targets:
                if isinstance(t, ast.Attribute) and (
                        self._history_subscript(t.value)
                        or (isinstance(t.value, ast.Name)
                            and t.value.id in aliases)):
                    out.append(unit.finding(
                        self, t,
                        f"assignment to HistoryRow field '{t.attr}' after "
                        f"append — rows are immutable outside the "
                        f"controller/cluster drivers; derive metrics "
                        f"instead of patching history"))

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_block(unit, node, out)
        return out


@register_rule
class GoldenImportBan(Rule):
    """Golden-trace-critical modules must not even import nondeterminism
    sources — the standing-notes invariant, machine-checked."""
    id = "R305"
    title = "banned import in a golden-trace-critical module"
    scope = GOLDEN_MODULES

    _BANNED = {"random", "time", "datetime", "uuid", "secrets"}

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            names: list[str] = []
            if isinstance(node, ast.Import):
                names = [a.name.split(".")[0] for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                names = [node.module.split(".")[0]]
            for name in names:
                if name in self._BANNED:
                    out.append(unit.finding(
                        self, node,
                        f"golden-trace-critical module imports {name!r} — "
                        f"the four golden traces pin this module's "
                        f"decisions byte-for-byte; nondeterminism sources "
                        f"are banned here outright (see "
                        f"docs/golden-traces.md)"))
        return out


# ---------------------------------------------------------------------------
# U — units
# ---------------------------------------------------------------------------

MB, SLOTS, SECONDS = "MB", "slots", "seconds"

_SEC_PARTS = {"s", "sec", "secs", "seconds", "duration", "downtime"}
_SLOT_PARTS = {"slots", "slot", "cores", "core", "cpus", "parallelism"}
_MB_PARTS = {"mb", "mem", "memory", "payload"}


def unit_hint(name: str | None) -> str | None:
    """The unit a parameter/argument name conventionally carries, or None
    when the convention is silent (``rate``, ``n``, ``factor``...)."""
    if not name:
        return None
    parts = name.lower().split("_")
    if parts[-1] in ("factor", "frac", "fraction", "share", "ratio"):
        return None                    # dimensionless multipliers
    if any(p in _MB_PARTS for p in parts):
        return MB
    if parts[-1] in _SEC_PARTS or any(p in ("duration", "downtime", "seconds")
                                      for p in parts):
        return SECONDS
    if any(p in _SLOT_PARTS for p in parts) or parts[-1] == "cpu" \
            or parts[0] == "cpu":
        return SLOTS
    return None


@register_rule
class UnitMixing(Rule):
    """MB / slots / seconds crossing a call boundary: an argument whose
    name conventionally carries one unit bound to a parameter that
    conventionally carries another."""
    id = "U401"
    title = "MB/slots/seconds unit mixing at a call site"
    severity = "warning"
    scope = ("src/repro/core/", "src/repro/scenarios/",
             "src/repro/migration/")

    def __init__(self) -> None:
        self._sigs: dict[str, tuple[list[str], bool]] = {}

    def prepare(self, units) -> None:
        # collect (params, is_method) per function name across the linted
        # tree; a name defined twice with different param lists is dropped
        # (ambiguous — stay conservative)
        sigs: dict[str, tuple[list[str], bool] | None] = {}
        for unit in units:
            for node in ast.walk(unit.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                params = [a.arg for a in node.args.args]
                is_method = bool(params) and params[0] in ("self", "cls")
                if is_method:
                    params = params[1:]
                prev = sigs.get(node.name)
                if node.name in sigs and (prev is None
                                          or prev[0] != params):
                    sigs[node.name] = None
                else:
                    sigs[node.name] = (params, is_method)
        self._sigs = {k: v for k, v in sigs.items() if v is not None}

    def _check(self, unit: FileUnit, call: ast.Call, param: str,
               arg: ast.AST, out: list[Finding]) -> None:
        want = unit_hint(param)
        got = unit_hint(terminal_name(arg))
        if want and got and want != got:
            out.append(unit.finding(
                self, arg,
                f"argument '{terminal_name(arg)}' ({got}) bound to "
                f"parameter '{param}' ({want}) — MB, CPU slots and "
                f"seconds must not cross a call boundary unconverted"))

    def visit(self, unit: FileUnit) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(unit.tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if kw.arg is not None:
                    self._check(unit, node, kw.arg, kw.value, out)
            fname = terminal_name(node.func)
            sig = self._sigs.get(fname) if fname else None
            if sig is not None:
                params, _is_method = sig
                for param, arg in zip(params, node.args):
                    if isinstance(arg, (ast.Name, ast.Attribute)):
                        self._check(unit, node, param, arg, out)
        return out
