"""The one event generator that every traffic mix runs through.

NEXmark events (Tucker et al.): persons, auctions and bids, keyed by seller
(persons, auctions) or bidder (bids), four int32 payload words each, with
the auction id in word 2 of auctions and bids.  Event time is the tick's
time.  What varies between deployments is data in the configuration's
``stream`` group: the kind mix, the keyspace and the key distribution.

Every seed draws the same sizes: each tick's count of every kind and of
hot keys is fixed by the mix, and only which keys, payloads and positions
is drawn from the seed.  So the work a tick does is the same from seed to
seed, and two seeds differ only as two samples of the same traffic.
"""
from __future__ import annotations

import time

import numpy as np

KINDS = ("person", "auction", "bid")
N_AUCTIONS = 10_000          # auction ids carried in payload word 2
PAYLOAD_MAX = 10_000         # payload words are drawn from [0, PAYLOAD_MAX)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of a run seed (any integer)."""
    return np.random.default_rng([seed % (1 << 64), stream])


def _split(n: int, weights: np.ndarray) -> np.ndarray:
    """Largest-remainder split of ``n`` items by ``weights``."""
    exact = n * weights / weights.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


class EventGen:
    """Callable ``(n, now_s) -> (key, value, ts, kind)`` arrays.

    ``stream``: ``{"mix": {kind: weight}, "keys": {"distribution":
    "uniform"} | {"distribution": "hot_set", "hot_fraction": f,
    "hot_keys": h}}``; keys are drawn from ``[0, keyspace)``, a hot set
    being ``[0, hot_keys)``; each event carries ``words`` int32 words.

    Every batch handed out is kept in ``emitted`` for the check, and the
    host seconds spent generating in ``seconds``."""

    def __init__(self, stream: dict, keyspace: int, words: int, seed: int):
        self.weights = np.array([float(stream["mix"].get(k, 0))
                                 for k in KINDS])
        self.keys = stream["keys"]
        self.keyspace = int(keyspace)
        self.words = int(words)
        self.rng = seed_rng(seed, 1)
        self.emitted: list[tuple[np.ndarray, ...]] = []
        self.seconds = 0.0

    def _draw_keys(self, n: int) -> np.ndarray:
        dist = self.keys["distribution"]
        if dist == "uniform":
            return self.rng.integers(0, self.keyspace, n)
        if dist != "hot_set":
            raise ValueError(f"unknown key distribution {dist!r}")
        n_hot = int(round(n * float(self.keys["hot_fraction"])))
        keys = np.concatenate([
            self.rng.integers(0, int(self.keys["hot_keys"]), n_hot),
            self.rng.integers(0, self.keyspace, n - n_hot)])
        self.rng.shuffle(keys)
        return keys

    def __call__(self, n: int, now_s: float) -> tuple[np.ndarray, ...]:
        t0 = time.perf_counter()
        kind = np.repeat(np.arange(len(KINDS), dtype=np.int8),
                         _split(n, self.weights))
        self.rng.shuffle(kind)
        key = self._draw_keys(n).astype(np.int64)
        value = self.rng.integers(0, PAYLOAD_MAX, (n, self.words),
                                  dtype=np.int32)
        carries_auction = kind != KINDS.index("person")
        value[carries_auction, 2] = self.rng.integers(
            0, N_AUCTIONS, int(carries_auction.sum()), dtype=np.int32)
        ts = np.full(n, float(now_s))
        ev = (key, value, ts, kind)
        self.emitted.append(ev)
        self.seconds += time.perf_counter() - t0
        return ev
