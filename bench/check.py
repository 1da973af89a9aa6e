"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, replayed over the same recorded inputs.

Every number compared is a count of disagreements, and every limit is 0:

* ``routing_mismatch``  events a task processed that are not, in order,
  the events the plain key partitioning sends it (steady cells), or whose
  key belongs to another task (cells that rescale);
* ``output_mismatch``   output rows of the operator, per processed batch,
  that the reference does not emit (multiset difference);
* ``state_mismatch``    live entries whose presence, weight or payload
  differs from the reference's, over all tasks;
* ``partition_mismatch`` entries held by a task other than the one their
  key hashes to.
"""
from __future__ import annotations

import numpy as np

GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def hash_partition(keys: np.ndarray, p: int) -> np.ndarray:
    """The system's documented key partitioning (Fibonacci hashing of the
    key, Flink's key-group role): task of each key among ``p``."""
    h = np.asarray(keys).astype(np.uint64) * GOLDEN
    h ^= h >> np.uint64(31)
    return (h >> np.uint64(1)).astype(np.int64) % p


def _rows(ev: tuple) -> np.ndarray:
    key, value, ts, kind = ev
    m = np.empty((len(key), 3 + value.shape[1]), np.int64)
    m[:, 0] = key
    m[:, 1] = np.asarray(ts, np.float64).view(np.int64)
    m[:, 2] = kind
    m[:, 3:] = value
    return np.ascontiguousarray(m)


def multiset_difference(a: tuple, b: tuple) -> int:
    """Rows in ``a`` or ``b`` that the other lacks, counted with
    multiplicity."""
    ra, rb = _rows(a), _rows(b)
    if len(ra) == len(rb) and np.array_equal(ra, rb):
        return 0
    both = np.concatenate([ra, rb])
    view = both.view(np.dtype((np.void, both.shape[1] * 8))).ravel()
    _, inv = np.unique(view, return_inverse=True)
    net = np.bincount(inv.ravel(), weights=np.r_[np.ones(len(ra)),
                                                 -np.ones(len(rb))])
    return int(np.abs(net).sum())


def state_difference(got: tuple, ref, want: tuple) -> int:
    """Entries on which the system's live state ``got`` (sorted keys,
    weights, values) and the reference's ``want`` disagree.  An entry
    only the reference holds counts unless the system may have dropped it
    (``ref.must_keep``)."""
    gk, gw, gv = got
    wk, ww, wv = want
    bad = int(np.count_nonzero(gk[1:] == gk[:-1]))     # one key twice
    pos = np.searchsorted(wk, gk)
    posc = np.minimum(pos, max(len(wk) - 1, 0))
    hit = (pos < len(wk)) & (wk[posc] == gk) if len(wk) else \
        np.zeros(len(gk), bool)
    bad += int(np.count_nonzero(~hit))                  # only the system
    gi, wi = np.flatnonzero(hit), posc[hit]
    bad += int(np.count_nonzero((gw[gi] != ww[wi])
                                | (gv[gi] != wv[wi]).any(axis=1)))
    only_ref = np.ones(len(wk), bool)
    only_ref[wi] = False
    bad += int(np.count_nonzero(ref.must_keep(wk[only_ref])))
    return bad


def merged(snaps: list[dict]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    keys = np.concatenate([np.asarray(s["keys"], np.int64) for s in snaps])
    w = np.concatenate([np.asarray(s["weights"], np.int64) for s in snaps])
    v = np.concatenate([np.asarray(s["vals"], np.int32) for s in snaps])
    order = np.argsort(keys, kind="stable")
    return keys[order], w[order], v[order]


def partition_difference(snaps: list[dict], refmod) -> int:
    p = len(snaps)
    return sum(int(np.count_nonzero(
        hash_partition(refmod.partition_key(s["keys"]), p) != i))
        for i, s in enumerate(snaps))


class Check:
    """Replays a recorded log through a reference and counts."""

    def __init__(self, dep, weights: str = "exact"):
        self.refmod = dep.ref
        self.ref = dep.ref.Reference(dep.config, dep.initial, weights)
        self.counts = {"routing_mismatch": 0, "output_mismatch": 0,
                       "state_mismatch": 0, "partition_mismatch": 0}

    def batch(self, inp: tuple, out: tuple) -> None:
        self.counts["output_mismatch"] += multiset_difference(
            out, self.ref.process(*inp))

    def state(self, snaps: list[dict]) -> None:
        self.counts["state_mismatch"] += state_difference(
            merged(snaps), self.ref, self.ref.state())
        self.counts["partition_mismatch"] += partition_difference(
            snaps, self.refmod)

    def replay(self, log: list[tuple]) -> None:
        for entry in log:
            if entry[0] == "batch":
                self.batch(entry[2], entry[3])
            elif entry[0] == "state":
                self.state(entry[1])

    def routing_prefix(self, log: list[tuple], emitted: list[tuple],
                       p: int) -> None:
        """Steady cells: each task processed, in order, a prefix of the
        events the partitioning routes to it."""
        ev = tuple(np.concatenate(c) for c in zip(*emitted))
        part = hash_partition(ev[0], p)
        got = [[] for _ in range(p)]
        for entry in log:
            if entry[0] == "batch":
                got[entry[1]].append(entry[2])
        bad = 0
        for i in range(p):
            routed = _rows(tuple(c[part == i] for c in ev))
            if not got[i]:
                continue
            mine = _rows(tuple(np.concatenate(c) for c in zip(*got[i])))
            n = min(len(mine), len(routed))
            bad += len(mine) - n
            bad += int(np.count_nonzero((mine[:n] != routed[:n]).any(axis=1)))
        self.counts["routing_mismatch"] += bad

    def routing_keys(self, log: list[tuple]) -> None:
        """Cells that rescale: every processed event's key belongs to the
        task that processed it (at the parallelism of that moment)."""
        p = None
        for entry in log:
            if entry[0] == "parallelism":
                p = entry[1]
            elif entry[0] == "batch":
                key = entry[2][0]
                self.counts["routing_mismatch"] += int(np.count_nonzero(
                    hash_partition(key, p) != entry[1]))


def report(counts: dict) -> dict:
    """The numbers compared, each beside its limit (exact: 0)."""
    return {name: {"value": int(v), "limit": 0}
            for name, v in counts.items()}
