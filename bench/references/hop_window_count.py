"""Plain reference of a hopping-window count per key with the hot item of
each window (NEXmark q5 as the Flink NEXmark suite's ``q5.sql`` runs it).

Windows are ``[k * slide, k * slide + size)``; a window's id is the index
of its end in slides, ``k + size / slide``.  Every event counts once in
each of the ``size / slide`` windows that contain it.  State per (key,
window): word 0 the count, the other words 0; its weight counts the
batches that wrote it (the operator writes each of a batch's (key,
window) pairs once).  Output of each batch (the combiner): for every
window the batch touched, the keys whose new count equals the largest
new count of the batch in that window, as rows keyed by window id with
value ``[key, count, 0, ...]``, the batch's earliest timestamp and kind
0.  Hot items: for each window, the lowest key at its largest count, the
count and the number of keys at it, as a row keyed by window id, value
``[key, count, ties, 0, ...]``, stamped with the window's end.

The operator's input is the source's bids keyed by auction (``route``).
Windows whose end the watermark has passed may be dropped by the system;
an event's timestamp is at most one tick behind the watermark, so
windows ending more than one slide after the newest event must be kept.

Dense arrays, one per window: no sorting of state, no store, no
partitioning, no combiner.  Imports nothing of the system under test.
"""
from __future__ import annotations

import numpy as np

from bench.references import bf16_round

WINDOW_BITS = 20             # state key = key << 20 | window id
BID = 2                      # NEXmark's event kinds: person, auction, bid


def encode(key: np.ndarray, wid: np.ndarray) -> np.ndarray:
    return (np.asarray(key, np.int64) << WINDOW_BITS) \
        | (np.asarray(wid, np.int64) & ((1 << WINDOW_BITS) - 1))


def partition_key(state_keys: np.ndarray) -> np.ndarray:
    """The event key a state entry belongs to."""
    return np.asarray(state_keys, np.int64) >> WINDOW_BITS


def route(key: np.ndarray, value: np.ndarray, ts: np.ndarray,
          kind: np.ndarray) -> tuple:
    """What the operator receives of the source's events: every bid,
    keyed by its auction (payload word 2)."""
    bid = np.asarray(kind) == BID
    return (np.asarray(value[bid, 2], np.int64), value[bid], ts[bid],
            kind[bid])


def initial_state(config: dict, rng: np.random.Generator
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Empty: the run starts at sim time 0 with no window open."""
    words = int(config["payload_words"])
    return (np.empty(0, np.int64), np.empty(0, np.int64),
            np.empty((0, words), np.int32))


class Reference:
    """``weights``: "exact" counts every event; "per_batch" counts a key
    once per batch in each of its windows however many of the batch's
    events it has (the exactly-once guarantee broken); "bfloat16" keeps
    counts and weights in bfloat16."""

    def __init__(self, config: dict, initial: tuple, weights: str = "exact"):
        params = config["operator_params"]
        self.size = float(params["size_s"])
        self.slide = float(params["slide_s"])
        self.per_event = int(round(self.size / self.slide))
        self.keys = int(config["auctions"])
        self.words = int(config["payload_words"])
        self.weights = weights
        self.count: dict[int, np.ndarray] = {}
        self.weight: dict[int, np.ndarray] = {}
        self.newest_ts = -np.inf
        if len(initial[0]):
            raise ValueError("the hopping-window count starts empty")

    def _window(self, wid: int) -> tuple[np.ndarray, np.ndarray]:
        if wid not in self.count:
            self.count[wid] = np.zeros(self.keys, np.int64)
            self.weight[wid] = np.zeros(self.keys, np.int64)
        return self.count[wid], self.weight[wid]

    def process(self, key, value, ts, kind):
        """One batch as the operator receives it: its output rows."""
        first = np.floor(ts / self.slide).astype(np.int64) + 1
        wid = (first[:, None] + np.arange(self.per_event)).ravel()
        keys = np.repeat(key, self.per_event)
        out_key, out_val = [], []
        for w in np.unique(wid):
            count, weight = self._window(int(w))
            k, n = np.unique(keys[wid == w], return_counts=True)
            count[k] += 1 if self.weights == "per_batch" else n
            weight[k] += 1
            if self.weights == "bfloat16":
                count[k] = bf16_round(count[k])
                weight[k] = bf16_round(weight[k])
            top = k[count[k] == count[k].max()]
            out_key.append(np.full(len(top), w, np.int64))
            v = np.zeros((len(top), self.words), np.int32)
            v[:, 0] = top
            v[:, 1] = count[k].max()
            out_val.append(v)
        self.newest_ts = max(self.newest_ts, float(ts.max()))
        keys = np.concatenate(out_key)
        return (keys, np.concatenate(out_val), np.full(len(keys), ts.min()),
                np.zeros(len(keys), np.int8))

    def state(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Every (key, window) with a count: sorted (keys, weights,
        values)."""
        ks, ws, cs = [], [], []
        for wid, count in self.count.items():
            idx = np.flatnonzero(self.weight[wid])
            ks.append(encode(idx, np.full(len(idx), wid)))
            ws.append(self.weight[wid][idx])
            cs.append(count[idx])
        if not ks:
            return initial_state({"payload_words": self.words}, None)
        keys = np.concatenate(ks)
        order = np.argsort(keys, kind="stable")
        vals = np.zeros((len(keys), self.words), np.int32)
        vals[:, 0] = np.concatenate(cs)[order]
        return keys[order], np.concatenate(ws)[order], vals

    def must_keep(self, keys: np.ndarray) -> np.ndarray:
        """Windows that end more than one slide after the newest event."""
        wid = np.asarray(keys, np.int64) & ((1 << WINDOW_BITS) - 1)
        return wid * self.slide > self.newest_ts + self.slide

    def closed(self, after: float, upto: float):
        """Hot-item rows of the windows that end in ``(after, upto]``."""
        wids = sorted(w for w in self.count
                      if after < w * self.slide <= upto)
        vals = np.zeros((len(wids), self.words), np.int32)
        for i, w in enumerate(wids):
            c = self.count[w]
            m = c.max()
            vals[i, 0] = np.flatnonzero(c == m)[0]
            vals[i, 1] = m
            vals[i, 2] = np.count_nonzero(c == m)
        keys = np.array(wids, np.int64)
        return (keys, vals, keys * self.slide, np.zeros(len(wids), np.int8))
