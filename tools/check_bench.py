#!/usr/bin/env python
"""Schema check for committed bench artifacts.

Dispatches on the artifact's ``bench`` tag:

* ``cluster_fleet`` — BENCH_cluster.json, the fleet-driver bench

CI runs the bench and then this checker; any drift in the emitted
schema — renamed keys, wrong types, impossible counts — fails the build
instead of silently producing an unplottable artifact.

    python tools/check_bench.py [ARTIFACT.json]
"""
from __future__ import annotations

import argparse
import json
import sys

SCHEMA_VERSION = 1

# key -> required type(s); bool is an int subclass, so exclude it where
# a genuine number is meant
FLEET_RUN_KEYS = {
    "tenants": int,
    "windows": int,
    "tenant_windows": int,
    "admission": str,
    "denied_tenant_windows": int,
    "deferred_tenant_windows": int,
    "preempted_tenant_windows": int,
    "policy_steps": int,
    "peak_cpu": int,
    "peak_mem_mb": (int, float),
    "cluster_cpu_slots": int,
    "cluster_memory_mb": (int, float),
    "seconds": (int, float),
    "tenant_windows_per_s": (int, float),
    "driver": str,
    "seed": int,
}

def _check_run_keys(run: dict, i: int, schema: dict) -> list[str]:
    errors = []
    for key, typ in schema.items():
        if key not in run:
            errors.append(f"runs[{i}] missing key {key!r}")
        elif not isinstance(run[key], typ) or isinstance(run[key], bool):
            want = typ.__name__ if isinstance(typ, type) \
                else "/".join(t.__name__ for t in typ)
            errors.append(f"runs[{i}][{key!r}] has type "
                          f"{type(run[key]).__name__}, want {want}")
    return errors


def _check_common(data) -> tuple[list[str], list]:
    if not isinstance(data, dict):
        return ["top level is not an object"], []
    errors = []
    if data.get("schema_version") != SCHEMA_VERSION:
        errors.append(f"schema_version != {SCHEMA_VERSION}: "
                      f"{data.get('schema_version')!r}")
    runs = data.get("runs")
    if not isinstance(runs, list) or not runs:
        return errors + ["runs is not a non-empty list"], []
    return errors, runs


def check_cluster_fleet(data) -> list[str]:
    errors, runs = _check_common(data)
    for i, run in enumerate(runs):
        if not isinstance(run, dict):
            errors.append(f"runs[{i}] is not an object")
            continue
        key_errors = _check_run_keys(run, i, FLEET_RUN_KEYS)
        if key_errors:
            errors += key_errors
            continue
        # internal consistency: the headline must be derivable
        if run["tenant_windows"] != run["tenants"] * run["windows"]:
            errors.append(f"runs[{i}]: tenant_windows != tenants * windows")
        if run["seconds"] <= 0 or run["tenant_windows_per_s"] <= 0:
            errors.append(f"runs[{i}]: non-positive throughput")
        if run["peak_cpu"] > run["cluster_cpu_slots"]:
            errors.append(f"runs[{i}]: peak_cpu exceeds the cluster")
        if run["peak_mem_mb"] > run["cluster_memory_mb"] + 1e-9:
            errors.append(f"runs[{i}]: peak_mem_mb exceeds the cluster")
        for key in ("denied_tenant_windows", "deferred_tenant_windows",
                    "preempted_tenant_windows", "policy_steps"):
            if run[key] < 0:
                errors.append(f"runs[{i}][{key!r}] is negative")
    return errors


CHECKERS = {
    "cluster_fleet": check_cluster_fleet,
}

# headline metric per bench kind, printed on success
HEADLINES = {
    "cluster_fleet": lambda d: max(r["tenant_windows_per_s"]
                                   for r in d["runs"]),
}


def check(data) -> list[str]:
    if not isinstance(data, dict):
        return ["top level is not an object"]
    kind = data.get("bench")
    checker = CHECKERS.get(kind)
    if checker is None:
        return [f"unknown bench kind {kind!r} "
                f"(want one of {sorted(CHECKERS)})"]
    return checker(data)


def _load(path: str):
    with open(path) as f:
        return json.load(f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("artifact", nargs="?", default="BENCH_cluster.json")
    args = ap.parse_args()
    try:
        data = _load(args.artifact)
    except (OSError, json.JSONDecodeError) as e:
        print(f"check_bench: cannot read {args.artifact}: {e}")
        return 1
    errors = check(data)
    for e in errors:
        print(f"check_bench: {args.artifact}: {e}")
    if not errors:
        extra = f", headline {HEADLINES[data['bench']](data):.3f}"
        print(f"check_bench: {args.artifact}: ok ({len(data['runs'])} runs, "
              f"schema v{SCHEMA_VERSION}{extra})")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
