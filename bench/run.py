"""Run one benchmark cell once on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is the
result (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` the per-layer metrics and a ``breakdown``, and last the
numbers compared with their limits); standard error ends with the same
numbers.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

# the checkout's root and the system's sources, in place of this file's
# own directory (whose module names would shadow others)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import pathlib  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench import harness
    cell = harness.resolve(pathlib.Path(ROOT), args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    harness.emit(result)


if __name__ == "__main__":
    main()
