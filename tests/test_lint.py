"""reprolint: the rule panel against its fixtures, baseline round-trip,
and the CLI contract (exit codes, JSON schema, self-check).

The fixture files under ``tools/lint/fixtures/`` are the ground truth:
each declares a pretend path (``# as: src/repro/...``) and annotates
every expected finding with ``# expect: RULE`` on its line.  The test
suite re-runs them through :func:`lint_source` (the same entry point the
CLI self-check uses) so a rule regression fails here *and* in CI's
``--self-check`` step.
"""
import json
import pathlib
import re
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))           # tools/ is not an installed package

from tools.lint.core import (all_rules, lint_source, load_baseline,  # noqa: E402
                             split_new, write_baseline)

FIXTURES = REPO / "tools" / "lint" / "fixtures"
_AS = re.compile(r"^#\s*as:\s*(\S+)\s*$", re.MULTILINE)
_EXPECT = re.compile(r"#\s*expect:\s*([A-Z][0-9]+(?:\s*,\s*[A-Z][0-9]+)*)")


def fixture_cases():
    for p in sorted(FIXTURES.glob("*.py")):
        src = p.read_text()
        m = _AS.search(src)
        relpath = m.group(1) if m else f"tools/lint/fixtures/{p.name}"
        expected = set()
        for i, line in enumerate(src.splitlines(), 1):
            em = _EXPECT.search(line)
            if em:
                for rule in re.split(r"\s*,\s*", em.group(1)):
                    expected.add((i, rule))
        yield pytest.param(src, relpath, expected, id=p.stem)


def run_cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.lint", *args],
                          cwd=cwd, capture_output=True, text=True)


# --------------------------------------------------------------- rule panel

@pytest.mark.parametrize("src,relpath,expected", fixture_cases())
def test_fixture_findings_exact(src, relpath, expected):
    """Every annotated line fires exactly its rule; nothing else fires."""
    got = {(f.line, f.rule) for f in lint_source(src, relpath).findings}
    assert got == expected


def test_every_rule_has_a_known_bad_fixture():
    """The fixture suite exercises the WHOLE panel — a new rule without a
    fixture fails here before it ships unverified."""
    covered = set()
    for _src, _rel, expected in (p.values for p in fixture_cases()):
        covered |= {rule for _line, rule in expected}
    assert covered == {r.id for r in all_rules()}


def test_suppression_counted_not_hidden():
    src = ("import numpy as np\n"
           "def f(xs):\n"
           "    return np.argsort(xs)  # reprolint: ignore[D103]\n")
    res = lint_source(src, "src/repro/core/x.py")
    assert res.findings == [] and res.suppressed == 1
    # a suppression for a DIFFERENT rule does not silence this one
    src2 = src.replace("[D103]", "[F201]")
    res2 = lint_source(src2, "src/repro/core/x.py")
    assert [f.rule for f in res2.findings] == ["D103"]


def test_scope_pretend_paths():
    """The same source fires in sim scope and stays quiet outside it."""
    src = "import numpy as np\norder = np.argsort([3, 1, 2])\n"
    assert [f.rule for f in
            lint_source(src, "src/repro/core/x.py").findings] == ["D103"]
    assert lint_source(src, "src/repro/models/x.py").findings == []


# ----------------------------------------------------------------- baseline

def test_baseline_round_trip(tmp_path):
    src = ("import numpy as np\n"
           "def f(xs):\n"
           "    return np.argsort(xs)\n")
    findings = lint_source(src, "src/repro/core/x.py").findings
    assert findings
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), findings)
    new, old = split_new(findings, load_baseline(str(bl)))
    assert new == [] and old == findings


def test_baseline_is_line_shift_resilient():
    """Keys are rule:path:stripped-line — inserting unrelated lines above
    a grandfathered finding must not make it 'new'."""
    src = "import numpy as np\ndef f(xs):\n    return np.argsort(xs)\n"
    shifted = "import numpy as np\n\n\n\ndef f(xs):\n    return np.argsort(xs)\n"
    a = lint_source(src, "src/repro/core/x.py").findings
    b = lint_source(shifted, "src/repro/core/x.py").findings
    assert a[0].line != b[0].line and a[0].key == b[0].key


def test_baseline_multiset_budget():
    """Two identical violations with one baselined: exactly one is new."""
    from collections import Counter
    src = ("import numpy as np\n"
           "def f(xs):\n"
           "    return np.argsort(xs)\n"
           "def g(xs):\n"
           "    return np.argsort(xs)\n")
    findings = lint_source(src, "src/repro/core/x.py").findings
    assert len(findings) == 2 and findings[0].key == findings[1].key
    new, old = split_new(findings, Counter({findings[0].key: 1}))
    assert len(new) == 1 and len(old) == 1


# ---------------------------------------------------------------------- CLI

def test_cli_clean_on_pr_head():
    """The committed baseline grandfathers everything that remains: the
    acceptance gate `python -m tools.lint --fail-on-new` exits 0."""
    r = run_cli("--fail-on-new", "--quiet")
    assert r.returncode == 0, r.stdout + r.stderr


def test_committed_baseline_holds_only_live_findings():
    """Every grandfathered entry still matches a finding in the tree.  An
    entry whose file or line has gone would otherwise linger and excuse
    a later finding that happens to share its key."""
    baseline = load_baseline(str(REPO / "tools" / "lint"
                                 / "baseline.json"))
    r = run_cli("--json")
    doc = json.loads(r.stdout)
    live = {f["key"] for f in doc["findings"] if f["baselined"]}
    assert doc["baselined"] == sum(baseline.values())
    assert live == set(baseline)


def test_cli_fails_with_location_on_injected_regression(tmp_path):
    bad = tmp_path / "regression.py"
    bad.write_text("import numpy as np\n"
                   "def f(xs):\n"
                   "    return np.argsort(xs)\n")
    r = run_cli(str(bad), "--fail-on-new")
    assert r.returncode == 1
    assert re.search(r"regression\.py:3:\d+: D103", r.stdout)


def test_cli_json_schema(tmp_path):
    bad = tmp_path / "regression.py"
    bad.write_text("import numpy as np\n"
                   "def f(xs):\n"
                   "    return np.argsort(xs)\n")
    r = run_cli(str(bad), "--json")
    doc = json.loads(r.stdout)
    assert doc["version"] == 1
    assert doc["files"] == 1 and doc["new"] == 1 and doc["baselined"] == 0
    assert isinstance(doc["suppressed"], int)
    assert doc["counts"] == {"D103": 1}
    (f,) = doc["findings"]
    assert set(f) == {"rule", "severity", "path", "line", "col", "message",
                      "key", "baselined"}
    assert f["rule"] == "D103" and f["line"] == 3 and f["baselined"] is False
    assert f["key"].startswith("D103:") and f["severity"] == "error"


def test_cli_self_check_passes():
    r = run_cli("--self-check", "--quiet")
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_list_rules_covers_panel():
    r = run_cli("--list-rules")
    assert r.returncode == 0
    for rule in all_rules():
        assert rule.id in r.stdout


def test_cli_unknown_rule_id_is_an_error():
    r = run_cli("--rules", "Z999")
    assert r.returncode != 0


# ------------------------------------------- interprocedural passes (PR 9)
# The whole-program passes (callgraph / T501 / T502 / B601 / A701) get the
# same fixture coverage as the per-file rules above; these tests pin the
# CROSS-FILE behaviour a single-file fixture cannot express.

from tools.lint.callgraph import build_callgraph  # noqa: E402
from tools.lint.core import (lint_units, parse_file,  # noqa: E402
                             parse_source)

UTIL = ("import time\n"
        "def now():\n"
        "    return time.time()\n")
GOLDEN_CALLER = ("from repro.core.zz_util import now\n"
                 "def stamp(batch):\n"
                 "    return now()\n")


def _units():
    return [parse_source(UTIL, "src/repro/core/zz_util.py"),
            parse_source(GOLDEN_CALLER, "src/repro/streaming/events.py")]


def test_callgraph_resolves_cross_module_calls_and_sinks():
    units = _units()
    cg = build_callgraph(units)
    f_now = "src/repro/core/zz_util.py::now"
    f_stamp = "src/repro/streaming/events.py::stamp"
    assert f_now in cg.edges[f_stamp]
    # alias expansion: ``time.time()`` surfaces as an external chain
    ext = {s.external for s in cg.sites_by_caller[f_now] if s.external}
    assert ("time", "time") in ext
    # reverse closure from the sink-bearing callee reaches the caller
    seen, parent = cg.reverse_closure({f_now})
    assert f_stamp in seen and parent[f_stamp] == f_now


def test_taint_pass_flags_cross_file_wall_clock_in_golden_module():
    findings = lint_units(_units(), all_rules({"T501"})).findings
    assert [(f.path, f.rule) for f in findings] == \
        [("src/repro/streaming/events.py", "T501")]
    assert "time.time" in findings[0].message


# ------------------------------------------- T501 obs carve-out (PR 10)
# The observability layer may read perf_counter for self-profiling; a
# golden module calling into it as a DISCARDED statement must stay clean,
# while a captured obs value — or the same shape outside src/repro/obs/ —
# is still a finding.  See tools/lint/taint.py module docstring.

OBS_TRACE = ("import time\n"
             "def zz_span(name):\n"
             "    time.perf_counter()\n")


def test_taint_obs_scope_discarded_call_is_exempt():
    # both a direct discarded call AND an indirect one through a local
    # helper: the carve-out works at propagation level, so the helper
    # itself never becomes tainted
    units = [parse_source(OBS_TRACE, "src/repro/obs/zz_trace.py"),
             parse_source(
                 "from repro.obs.zz_trace import zz_span\n"
                 "def _note():\n"
                 "    zz_span('w')\n"
                 "def stamp(batch):\n"
                 "    _note()\n"
                 "    zz_span('x')\n"
                 "    return len(batch)\n",
                 "src/repro/streaming/events.py")]
    assert lint_units(units, all_rules({"T501"})).findings == []


def test_taint_obs_scope_captured_value_still_flagged():
    units = [parse_source(OBS_TRACE, "src/repro/obs/zz_trace.py"),
             parse_source(
                 "from repro.obs.zz_trace import zz_span\n"
                 "def stamp(batch):\n"
                 "    return zz_span('x')\n",
                 "src/repro/streaming/events.py")]
    findings = lint_units(units, all_rules({"T501"})).findings
    assert [(f.path, f.rule) for f in findings] == \
        [("src/repro/streaming/events.py", "T501")]
    assert "time.perf_counter" in findings[0].message


def test_taint_obs_scope_is_path_scoped_not_shape_scoped():
    # the same write-only shape OUTSIDE src/repro/obs/ gets no carve-out:
    # a discarded call can still have arbitrary side effects, only the
    # audited obs package is trusted to be write-only
    units = [parse_source(OBS_TRACE, "src/repro/core/zz_trace.py"),
             parse_source(
                 "from repro.core.zz_trace import zz_span\n"
                 "def stamp(batch):\n"
                 "    zz_span('x')\n"
                 "    return len(batch)\n",
                 "src/repro/streaming/events.py")]
    findings = lint_units(units, all_rules({"T501"})).findings
    assert [(f.path, f.rule) for f in findings] == \
        [("src/repro/streaming/events.py", "T501")]


def test_emit_only_restricts_reporting_not_analysis():
    # the --changed-only contract: the whole program is still analyzed
    # (the cross-file taint fact comes from core/zz_util), but findings are
    # only REPORTED for the changed files.
    rules = all_rules({"T501", "D102"})
    golden_only = lint_units(_units(), rules,
                             emit_only={"src/repro/streaming/events.py"})
    assert [f.rule for f in golden_only.findings] == ["T501"]
    util_only = lint_units(_units(), rules,
                           emit_only={"src/repro/core/zz_util.py"})
    assert [f.rule for f in util_only.findings] == ["D102"]
    assert lint_units(_units(), rules, emit_only=set()).findings == []


def test_cli_changed_only_smoke():
    r = run_cli("--changed-only", "--quiet")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "changed reported" in r.stdout


def test_cli_changed_only_rejects_write_baseline():
    r = run_cli("--changed-only", "--write-baseline")
    assert r.returncode == 2


def test_parse_cache_reuses_units_across_runs():
    path = "src/repro/streaming/events.py"
    assert parse_file(path) is parse_file(path)


def test_bitwidth_symbolic_modulus_proves_low_field():
    guarded = ("import numpy as np\n"
               "_S = np.int64(45)\n"
               "def pack(srcs, keys):\n"
               "    n = len(srcs)\n"
               "    assert n < (1 << 18)\n"
               "    keys = keys % (np.int64(1) << _S)\n"
               "    return (np.arange(n) << _S) + keys\n")
    assert lint_source(guarded, "src/repro/state/zz.py").findings == []
    unguarded = guarded.replace("    assert n < (1 << 18)\n", "")
    assert [f.rule for f in
            lint_source(unguarded, "src/repro/state/zz.py").findings] \
        == ["B601"]


def test_escape_pass_tracks_aliasing_through_private_helper():
    src = ("import numpy as np\n"
           "class Box:\n"
           "    def __init__(self):\n"
           "        self._a = np.zeros(4)\n"
           "    def _live(self):\n"
           "        return self._a\n"
           "    def view(self):\n"
           "        return self._live()\n"
           "    def safe(self):\n"
           "        return self._live().copy()\n")
    findings = lint_source(src, "src/repro/state/zz.py").findings
    assert [(f.rule, f.line) for f in findings] == [("A701", 8)]
