"""Records the padded kernel shapes a cell launches, for the ``compile``
list of its traffic file (``bench/warmup.py`` compiles that list at
set-up).

Runs on the host alone.  The cell is set up as ``bench/run.py`` sets it
up, with the store on its device path and each kernel's jitted entry
stood in for by a numpy function of the same result, which sees the
padded operands of every launch.  What the store launches follows from
its own logic and the seeded data, not from which backend computes, so
these are the shapes a run on the chip launches.

    JAX_PLATFORMS=cpu python3 bench/shapes.py --workload q8.steady \
        --seeds 1,2,3 --steps 60

Prints the ``compile`` group as JSON: every pairing (``closure``) of the
sizes that set-up (warm ticks) and ``steps`` units of the window's work
after it (ticks, or rescales with the ticks between) launched, over the
seeds.  Take ``steps`` well above what a window of ``run_seconds`` holds.
``--merge`` adds the shapes of earlier outputs.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402

import numpy as np  # noqa: E402


def _join(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """int64 keys from the two int32 words of ``ops.split_keys``."""
    low = (np.asarray(lo).view(np.uint32) ^ np.uint32(0x80000000))
    return (np.asarray(hi, np.int64) << 32) | low.astype(np.int64)


class StandIns:
    """Numpy stand-ins for the kernels' jitted entries that keep every
    padded shape they are called with."""

    def __init__(self):
        self.seen = {"sorted_probe": set(), "window_agg": set()}

    def sorted_probe(self, t_hi, t_lo, q_hi, q_lo, *, interpret=False):
        self.seen["sorted_probe"].add((len(t_hi), len(q_hi)))
        table, queries = _join(t_hi, t_lo), _join(q_hi, q_lo)
        pos = np.searchsorted(table, queries, side="left")
        found = table[np.minimum(pos, len(table) - 1)] == queries
        return pos.astype(np.int32), found

    def window_agg(self, seg_ids, values, n_segments, *, interpret=False):
        seg_ids, values = np.asarray(seg_ids), np.asarray(values)
        self.seen["window_agg"].add((len(seg_ids), int(n_segments),
                                     values.shape[0]))
        ok = seg_ids >= 0
        return np.stack([np.bincount(seg_ids[ok], weights=row[ok],
                                     minlength=n_segments)
                         for row in values]).astype(np.float32)

    def install(self) -> None:
        import repro.kernels.sorted_probe.kernel as probe_kernel
        import repro.kernels.window_agg.kernel as agg_kernel
        probe_kernel.sorted_probe = self.sorted_probe
        agg_kernel.window_agg = self.window_agg


def closure(seen: dict) -> dict:
    """Every pairing of the sizes seen: each table size with each query
    size, each event size with each segment size up to it.  Which sizes
    meet in one launch varies from seed to seed; the sizes themselves
    vary less."""
    probe = seen.get("sorted_probe", set())
    agg = seen.get("window_agg", set())
    tables, queries = {t for t, _ in probe}, {q for _, q in probe}
    rows = {r for _, _, r in agg}
    events, segments = {e for e, _, _ in agg}, {s for _, s, _ in agg}
    return {"sorted_probe": [[t, q] for t in sorted(tables)
                             for q in sorted(queries)],
            "window_agg": [[e, s, r] for e in sorted(events)
                           for s in sorted(segments) if s <= e
                           for r in sorted(rows)]}


def record(cell, seeds, steps: int) -> dict:
    """The padded shapes launched, per kernel, as sets of tuples."""
    from bench import harness
    from repro.state import lsm
    stand = StandIns()
    stand.install()
    lsm.set_kernel_impl("interpret")
    for seed in seeds:
        run = harness.Run(cell, seed, 0.0, False, "interpret")
        cell.driver.setup(run)
        for _ in range(steps):
            cell.driver.step(run)
        run.dep.free()
        harness.log(f"seed {seed}: {sum(map(len, stand.seen.values()))} "
                    f"shapes so far")
    return stand.seen


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--merge", nargs="*", default=[])
    args = ap.parse_args()
    from bench import harness
    cell = harness.resolve(pathlib.Path(ROOT), args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    seen = record(cell, seeds, args.steps) if seeds else {}
    for path in args.merge:
        for k, v in json.loads(pathlib.Path(path).read_text()).items():
            seen.setdefault(k, set()).update(tuple(s) for s in v)
    print(json.dumps(closure(seen)))


if __name__ == "__main__":
    main()
