"""reprolint core: AST-based invariant linting for the repro codebase.

The repo's headline guarantees are *exactness* invariants — byte-identical
golden traces, scalar-vs-vectorized fleet oracles, bit-for-bit repack
reconciliation — and the nastiest shipped bugs (``Cluster.fits`` float-drift
phantom denials, the LSM stale-snapshot aliasing) were classes of error a
repo-aware static pass can flag before review.  This module is the
framework; the rule panel lives in :mod:`tools.lint.rules`:

* :class:`Rule` — one invariant check.  A rule declares an id
  (``D103``, ``F201``, ...), a severity, and a path *scope* (repo-relative
  prefixes it applies to) or *exemption* list; ``visit(ctx)`` walks the
  file's AST and yields :class:`Finding`\\ s.
* :class:`Finding` — (rule, path, line, col, message).  Its baseline
  ``key`` is ``rule:path:stripped-source-line`` — resilient to line
  renumbering, so unrelated edits don't churn the committed baseline.
* **Baseline** — a committed JSON multiset of finding keys
  (``tools/lint/baseline.json``) grandfathers findings that are real but
  deliberately not fixed.  ``--fail-on-new`` fails only on findings
  whose key is NOT in the baseline.
* **Suppression** — ``# reprolint: ignore[D103]`` on the offending line
  silences that rule there (bare ``# reprolint: ignore`` silences all);
  suppressions are counted and reported so they can't hide silently.

Rules apply their path scope only to files under ``src/repro/`` — any
other path (test snippets, the self-check fixtures) gets the full panel,
with :func:`lint_source` accepting a *pretend* path so fixtures can also
exercise the scoping logic itself.
"""
from __future__ import annotations

import ast
import json
import os
import re
from collections import Counter
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(REPO, "tools", "lint", "baseline.json")
DEFAULT_PATHS = (os.path.join("src", "repro"),)

_SUPPRESS = re.compile(r"#\s*reprolint:\s*ignore(?:\[([A-Z0-9,\s]+)\])?")


@dataclass(frozen=True)
class Finding:
    """One rule violation at a file:line."""
    rule: str
    severity: str            # "error" | "warning"
    path: str                # repo-relative, forward slashes
    line: int                # 1-based
    col: int                 # 0-based
    message: str
    line_text: str = ""      # stripped source line (baseline key material)

    @property
    def key(self) -> str:
        """Baseline identity: stable across pure line-number shifts."""
        return f"{self.rule}:{self.path}:{self.line_text}"

    def to_dict(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message, "key": self.key}

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule} [{self.severity}] {self.message}")


@dataclass
class FileUnit:
    """One parsed source file handed to every applicable rule."""
    relpath: str             # repo-relative, forward slashes
    tree: ast.AST
    lines: list[str]         # source lines (1-based access via line_at)

    def in_repro(self) -> bool:
        return self.relpath.startswith("src/repro/")

    def line_at(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule: "Rule", node: ast.AST, message: str) -> Finding:
        return Finding(rule=rule.id, severity=rule.severity,
                       path=self.relpath, line=node.lineno,
                       col=node.col_offset, message=message,
                       line_text=self.line_at(node.lineno))


class Rule:
    """Base class for one invariant check.

    ``scope`` / ``exempt`` are repo-relative path prefixes.  The scope is
    enforced only for files under ``src/repro/`` — fixture and test
    snippets (any other path) always get the rule, and may opt into a
    pretend path via :func:`lint_source` to exercise the scoping.
    """
    id: str = "X000"
    title: str = ""
    severity: str = "error"
    scope: tuple[str, ...] = ()      # empty == everywhere (in src/repro)
    exempt: tuple[str, ...] = ()     # always wins over scope

    def applies(self, relpath: str) -> bool:
        if any(relpath.startswith(e) for e in self.exempt):
            return False
        if not relpath.startswith("src/repro/"):
            return True              # fixtures/tests get the full panel
        return not self.scope or any(relpath.startswith(s)
                                     for s in self.scope)

    def prepare(self, units: list[FileUnit]) -> None:
        """Optional whole-program pre-pass (e.g. signature collection)."""

    def visit(self, unit: FileUnit) -> list[Finding]:
        raise NotImplementedError


_RULES: dict[str, type[Rule]] = {}


def register_rule(cls: type[Rule]) -> type[Rule]:
    """Class decorator adding a rule to the panel (id must be unique)."""
    if cls.id in _RULES:
        raise ValueError(f"duplicate rule id {cls.id}")
    _RULES[cls.id] = cls
    return cls


def all_rules(only: set[str] | None = None) -> list[Rule]:
    """Fresh instances of the registered panel, sorted by id."""
    import tools.lint.rules    # noqa: F401  (registers the per-file panel)
    import tools.lint.taint    # noqa: F401  (T501/T502)
    import tools.lint.bitwidth  # noqa: F401  (B601)
    import tools.lint.effects  # noqa: F401  (A701)
    ids = sorted(_RULES)
    if only is not None:
        unknown = only - set(ids)
        if unknown:
            raise ValueError(f"unknown rule id(s): {', '.join(sorted(unknown))}"
                             f" (have: {', '.join(ids)})")
        ids = [i for i in ids if i in only]
    return [_RULES[i]() for i in ids]


# ---------------------------------------------------------------------------
# Shared AST helpers (used by the rule panel)
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> tuple[str, ...]:
    """The dotted-name chain of a Name/Attribute expression, outermost
    first: ``np.random.default_rng`` -> ('np', 'random', 'default_rng').
    Empty tuple for anything that is not a pure name chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return ()


def terminal_name(node: ast.AST) -> str | None:
    """The last identifier of a name chain (``self.used_mem`` ->
    ``used_mem``), or None for non-name expressions."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def identifiers(node: ast.AST) -> list[str]:
    """Every identifier mentioned anywhere inside an expression."""
    out: list[str] = []
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.append(n.id)
        elif isinstance(n, ast.Attribute):
            out.append(n.attr)
        elif isinstance(n, ast.keyword) and n.arg:
            out.append(n.arg)
    return out


# ---------------------------------------------------------------------------
# Program: the whole-unit set, with shared interprocedural state
# ---------------------------------------------------------------------------

class Program(list):
    """The list of :class:`FileUnit`\\ s for one lint run, carrying lazily
    built whole-program state shared by every interprocedural pass.  The
    call graph is built at most once per run no matter how many passes
    ask for it — the per-pass cost is the analysis, not the parse or the
    graph."""

    def __init__(self, units: list[FileUnit]) -> None:
        super().__init__(units)
        self._callgraph = None

    def callgraph(self):
        if self._callgraph is None:
            from tools.lint.callgraph import build_callgraph
            self._callgraph = build_callgraph(list(self))
        return self._callgraph


def get_callgraph(units: list[FileUnit]):
    """The shared call graph when ``units`` is a :class:`Program` (the
    normal case inside ``lint_units``); a fresh one otherwise."""
    if isinstance(units, Program):
        return units.callgraph()
    from tools.lint.callgraph import build_callgraph
    return build_callgraph(list(units))


# ---------------------------------------------------------------------------
# Running
# ---------------------------------------------------------------------------

def _suppressed_rules(line_text: str) -> set[str] | None:
    """None == no suppression; empty set == suppress everything."""
    m = _SUPPRESS.search(line_text)
    if m is None:
        return None
    if m.group(1) is None:
        return set()
    return {r.strip() for r in m.group(1).split(",") if r.strip()}


@dataclass
class LintResult:
    findings: list[Finding] = field(default_factory=list)
    suppressed: int = 0
    files: int = 0

    def extend(self, other: "LintResult") -> None:
        self.findings.extend(other.findings)
        self.suppressed += other.suppressed
        self.files += other.files


def _apply_suppressions(unit: FileUnit,
                        findings: list[Finding]) -> tuple[list[Finding], int]:
    kept: list[Finding] = []
    dropped = 0
    for f in findings:
        sup = _suppressed_rules(unit.line_at(f.line))
        if sup is not None and (not sup or f.rule in sup):
            dropped += 1
        else:
            kept.append(f)
    return kept, dropped


def lint_units(units: list[FileUnit],
               rules: list[Rule] | None = None,
               emit_only: set[str] | None = None) -> LintResult:
    """Run the panel.  ``emit_only`` restricts *reported* findings to the
    given relpaths while every unit still participates in whole-program
    pre-passes — the ``--changed-only`` contract: interprocedural facts
    come from the full program, the diff decides what is reported."""
    rules = rules if rules is not None else all_rules()
    if not isinstance(units, Program):
        units = Program(units)
    for rule in rules:
        rule.prepare(units)
    res = LintResult(files=len(units))
    for unit in units:
        if emit_only is not None and unit.relpath not in emit_only:
            continue
        found: list[Finding] = []
        for rule in rules:
            if rule.applies(unit.relpath):
                found.extend(rule.visit(unit))
        found.sort(key=lambda f: (f.line, f.col, f.rule))
        kept, dropped = _apply_suppressions(unit, found)
        res.findings.extend(kept)
        res.suppressed += dropped
    return res


def parse_source(src: str, relpath: str) -> FileUnit:
    tree = ast.parse(src, filename=relpath)
    return FileUnit(relpath=relpath.replace(os.sep, "/"), tree=tree,
                    lines=src.splitlines())


# (abspath) -> (mtime_ns, size, FileUnit): every rule AND every
# interprocedural pass in a process shares one parse per file — repeated
# lint entry points (CLI + self-check, test harnesses) hit the cache.
_PARSE_CACHE: dict[str, tuple[int, int, FileUnit]] = {}


def parse_file(relpath: str) -> FileUnit:
    absp = os.path.join(REPO, relpath)
    st = os.stat(absp)
    hit = _PARSE_CACHE.get(absp)
    if hit is not None and hit[0] == st.st_mtime_ns and hit[1] == st.st_size:
        return hit[2]
    with open(absp, encoding="utf-8") as f:
        unit = parse_source(f.read(), relpath)
    _PARSE_CACHE[absp] = (st.st_mtime_ns, st.st_size, unit)
    return unit


def lint_source(src: str, relpath: str,
                rules: list[Rule] | None = None) -> LintResult:
    """Lint one source string as if it lived at ``relpath`` (the fixture /
    unit-test entry point — pretend paths exercise rule scoping)."""
    return lint_units([parse_source(src, relpath)], rules)


def collect_files(paths: list[str]) -> list[str]:
    """Expand files/directories into a sorted repo-relative .py file list."""
    out: set[str] = set()
    for p in paths:
        absp = p if os.path.isabs(p) else os.path.join(REPO, p)
        if os.path.isfile(absp):
            out.add(os.path.relpath(absp, REPO))
        elif os.path.isdir(absp):
            for root, dirs, files in os.walk(absp):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.add(os.path.relpath(os.path.join(root, f), REPO))
        else:
            raise FileNotFoundError(f"no such lint path: {p}")
    return sorted(o.replace(os.sep, "/") for o in out)


def lint_paths(paths: list[str],
               rules: list[Rule] | None = None,
               emit_only: set[str] | None = None) -> LintResult:
    units = Program([parse_file(rel) for rel in collect_files(paths)])
    return lint_units(units, rules, emit_only=emit_only)


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

BASELINE_VERSION = 1


def baseline_counts(findings: list[Finding]) -> dict[str, int]:
    return dict(sorted(Counter(f.key for f in findings).items()))


def write_baseline(path: str, findings: list[Finding]) -> None:
    doc = {"version": BASELINE_VERSION,
           "comment": "Grandfathered reprolint findings. Keys are "
                      "rule:path:stripped-source-line; regenerate with "
                      "`python -m tools.lint --write-baseline` and commit "
                      "deliberately (docs/static-analysis.md).",
           "findings": baseline_counts(findings)}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")


def load_baseline(path: str) -> Counter:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if doc.get("version") != BASELINE_VERSION:
        raise ValueError(f"unsupported baseline version in {path}: "
                         f"{doc.get('version')!r}")
    counts = doc.get("findings", {})
    if not isinstance(counts, dict) \
            or not all(isinstance(v, int) and v > 0 for v in counts.values()):
        raise ValueError(f"malformed baseline {path}: 'findings' must map "
                         f"key -> positive count")
    return Counter(counts)


def split_new(findings: list[Finding],
              baseline: Counter) -> tuple[list[Finding], list[Finding]]:
    """(new, grandfathered): each baseline key absorbs up to its count."""
    budget = Counter(baseline)
    new: list[Finding] = []
    old: list[Finding] = []
    for f in findings:
        if budget[f.key] > 0:
            budget[f.key] -= 1
            old.append(f)
        else:
            new.append(f)
    return new, old
