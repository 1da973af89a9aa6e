"""The comparison's readings on the chip, for setting its limits: for each
seed, one run of the cell at its own size and load, then the recorded
inputs replayed through the plain reference three ways: exact (the lower
reading, the system against the reference), and as two controls put in
the system's place, with weights counted once per batch ("per_batch",
the exactly-once guarantee broken) and weights kept in bfloat16
("bfloat16").  The benchmark's own runs do not run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One JSON line per seed and reading on standard output.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402

READINGS = ("exact", "per_batch", "bfloat16")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench import harness
    cell = harness.resolve(pathlib.Path(ROOT), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.measure(cell, seed, args.seconds, False,
                              t_start=time.monotonic())
        for weights in READINGS:
            counts = cell.driver.check(run, weights).counts
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "reading": weights, "counts": counts,
                              "attempted": run.attempted,
                              "metrics": run.metrics}), flush=True)


if __name__ == "__main__":
    main()
