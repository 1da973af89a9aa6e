"""Plain reference of a tumbling-window equi-join (NEXmark q8).

Events of the left kind are stored under (key, left side, window) and
probe the right side of the same key and window; events of the right
kind the other way round.  A probe that finds the other side emits the
probing event with the stored payload of the other side.  State is a
Z-set: every write of a key adds one to its weight and its payload is
the newest one written.  Windows older than ``retention_windows`` behind
the newest window may be dropped by the system; the rest must be kept.

Dense tables, one per window: no sorting, no runs, no cache.  Imports
nothing of the system under test.
"""
from __future__ import annotations

import numpy as np

from bench.references import bf16_round, last_occurrence

KIND_IDS = {"person": 0, "auction": 1, "bid": 2}
WINDOW_BITS = 16             # state key = ((key * 4 + side) << 16) | window
SIDES = 4                    # the key reserves four side slots


def encode(key: np.ndarray, side: int, wid: np.ndarray) -> np.ndarray:
    return ((key.astype(np.int64) * SIDES + side) << WINDOW_BITS) \
        + (wid.astype(np.int64) % (1 << WINDOW_BITS))


def partition_key(state_keys: np.ndarray) -> np.ndarray:
    """The event key a state entry belongs to."""
    return (np.asarray(state_keys, np.int64) >> WINDOW_BITS) // SIDES


def initial_state(config: dict, rng: np.random.Generator
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Both sides of every pre-populated window, each key once, payloads
    drawn from ``rng``: sorted (keys, weights, values)."""
    k, words = int(config["keyspace"]), int(config["payload_words"])
    keys = [encode(np.arange(k), side, np.full(k, w))
            for side in (0, 1) for w in config["live_windows"]]
    keys = np.concatenate(keys)
    order = np.argsort(keys, kind="stable")
    vals = rng.integers(0, 2**31 - 1, (len(keys), words), dtype=np.int32)
    return keys[order], np.ones(len(keys), np.int64), vals


class Reference:
    """``weights``: "exact" counts every write; "bfloat16" rounds each
    weight to bfloat16 after every batch; "per_batch" counts a key once
    per batch however often the batch writes it (the exactly-once
    guarantee broken)."""

    def __init__(self, config: dict, initial: tuple, weights: str = "exact"):
        self.k = int(config["keyspace"])
        self.words = int(config["payload_words"])
        self.window_s = float(config["window_s"])
        self.retention = int(config["retention_windows"])
        self.left = KIND_IDS[config["join"]["left"]]
        self.right = KIND_IDS[config["join"]["right"]]
        self.weights = weights
        self.tables: dict[int, list[np.ndarray]] = {}
        self.newest_window = -1
        keys, w, v = initial
        wid = keys & ((1 << WINDOW_BITS) - 1)
        side = (keys >> WINDOW_BITS) % SIDES
        key = partition_key(keys)
        for win in np.unique(wid):
            t = self._table(int(win))
            for s in (0, 1):
                m = (wid == win) & (side == s)
                t[0][s, key[m]] = True
                t[1][s, key[m]] = w[m]
                t[2][s, key[m]] = v[m]

    def _table(self, wid: int) -> list[np.ndarray]:
        t = self.tables.get(wid)
        if t is None:
            t = [np.zeros((2, self.k), bool), np.zeros((2, self.k), np.int64),
                 np.zeros((2, self.k, self.words), np.int32)]
            self.tables[wid] = t
        self.newest_window = max(self.newest_window, wid)
        return t

    def _put(self, key, side, wid, value) -> None:
        for win in np.unique(wid):
            m = wid == win
            k, v = key[m], value[m]
            present, weight, vals = self._table(int(win))
            uq, last, cnt = last_occurrence(k)
            present[side, uq] = True
            add = np.ones_like(cnt) if self.weights == "per_batch" else cnt
            weight[side, uq] += add
            if self.weights == "bfloat16":
                weight[side, uq] = bf16_round(weight[side, uq])
            vals[side, uq] = v[last]

    def _get(self, key, side, wid):
        vals = np.zeros((len(key), self.words), np.int32)
        found = np.zeros(len(key), bool)
        for win in np.unique(wid):
            t = self.tables.get(int(win))
            if t is None:
                continue
            m = np.flatnonzero(wid == win)
            found[m] = t[0][side, key[m]]
            vals[m] = t[2][side, key[m]]
        return vals, found

    def process(self, key, value, ts, kind):
        """One batch as the operator receives it: its output rows."""
        wid = (ts // self.window_s).astype(np.int64)
        outs = []
        for kind_id, mine, other in ((self.left, 0, 1), (self.right, 1, 0)):
            m = np.flatnonzero(kind == kind_id)
            if not len(m):
                continue
            self._put(key[m], mine, wid[m], value[m])
            got, found = self._get(key[m], other, wid[m])
            sel = m[found]
            outs.append((key[sel], got[found], ts[sel], kind[sel]))
        if not outs:
            return (np.empty(0, np.int64), np.empty((0, self.words), np.int32),
                    np.empty(0), np.empty(0, np.int8))
        return tuple(np.concatenate(c) for c in zip(*outs))

    def state(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Every live entry: sorted (keys, weights, values)."""
        ks, ws, vs = [], [], []
        for wid, (present, weight, vals) in self.tables.items():
            for side in (0, 1):
                idx = np.flatnonzero(present[side])
                ks.append(encode(idx, side, np.full(len(idx), wid)))
                ws.append(weight[side, idx])
                vs.append(vals[side, idx])
        keys = np.concatenate(ks)
        order = np.argsort(keys, kind="stable")
        return keys[order], np.concatenate(ws)[order], \
            np.concatenate(vs)[order]

    def must_keep(self, keys: np.ndarray) -> np.ndarray:
        """Entries the system may not have dropped: windows within the
        retention of the newest window seen."""
        wid = keys & ((1 << WINDOW_BITS) - 1)
        return wid >= self.newest_window - self.retention
