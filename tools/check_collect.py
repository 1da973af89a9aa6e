#!/usr/bin/env python
"""Collection guard: the LSM/kernel suites must never collect zero tests.

A refactor that renames a module, breaks an import, or trips a module-
level skip/parametrize bug can zero out a whole test file while CI stays
green — "passed" because nothing ran.  This gate runs pytest collection
over the suites that lock down the columnar store and fails if any of
them yields no tests (or fewer than its pinned floor).

    python tools/check_collect.py
"""
from __future__ import annotations

import subprocess
import sys
from collections import Counter

# suite -> minimum collected tests.  The differential harness floor is
# the PR acceptance criterion (>=200 recorded random op sequences);
# the reprolint floor pins the 19-fixture parametrization (per-file
# rules AND the PR 9 interprocedural passes) plus the baseline/CLI
# contract and cross-file pass tests; the packed-key floor pins the
# bit-width/aliasing regression suite; the rest just must not vanish.
SUITES = {
    "tests/test_lsm.py": 1,
    "tests/test_kernels.py": 1,
    "tests/test_lsm_differential.py": 200,
    "tests/test_kernel_parity.py": 1,
    "tests/test_lint.py": 43,
    "tests/test_packed_key_bounds.py": 14,
    "tests/test_obs.py": 22,
}


def main() -> int:
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         *SUITES],
        capture_output=True, text=True)
    if proc.returncode not in (0, 5):
        print(proc.stdout)
        print(proc.stderr, file=sys.stderr)
        print(f"check_collect: pytest collection errored "
              f"(exit {proc.returncode})")
        return 1
    counts: Counter[str] = Counter()
    for line in proc.stdout.splitlines():
        if "::" in line:
            counts[line.split("::", 1)[0]] += 1
    failures = []
    for suite, floor in SUITES.items():
        got = counts.get(suite, 0)
        status = "ok" if got >= floor else f"FAIL (floor {floor})"
        print(f"check_collect: {suite}: {got} tests {status}")
        if got < floor:
            failures.append(suite)
    if failures:
        print(f"check_collect: {len(failures)} suite(s) under-collect")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
