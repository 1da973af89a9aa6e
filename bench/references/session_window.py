"""Plain reference of per-key session tracking (NEXmark q11).

State per key: word 0 the time of the key's last event, word 1 the
events in its current session, the other words as first written.  An
event more than ``gap_s`` after the key's last one closes the session:
the event is emitted (for a key that had state) and a new session
starts with it; otherwise the session's count goes up by one.  Every
event of a batch reads the state as it was before the batch, and the
newest event of a key in the batch writes it.  Weights count writes.

Dense arrays over the keyspace.  Imports nothing of the system under
test.
"""
from __future__ import annotations

import numpy as np

from bench.references import bf16_round, last_occurrence

TS_CAP = 2**30                # word 0 holds min(ts, 2^30)


def partition_key(state_keys: np.ndarray) -> np.ndarray:
    return np.asarray(state_keys, np.int64)


def key_rates(config: dict, events_per_s: float) -> np.ndarray:
    """Events a second of each key of the keyspace under the
    configuration's key distribution."""
    k = int(config["keyspace"])
    keys = config["stream"]["keys"]
    if keys["distribution"] == "uniform":
        return np.full(k, events_per_s / k)
    hot_f, hot = float(keys["hot_fraction"]), int(keys["hot_keys"])
    rate = np.full(k, (1.0 - hot_f) * events_per_s / k)
    rate[:hot] += hot_f * events_per_s / hot
    return rate


def initial_state(config: dict, rng: np.random.Generator
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Every key of the keyspace with its session record as the stream of
    ``history`` leaves it: a first write at time 0, then a Poisson number
    of events at the key's rate over ``history.seconds`` (each counted in
    the weight), the last one an exponential time before the end (word 0),
    and the events of the current session (word 1)."""
    k, words = int(config["keyspace"]), int(config["payload_words"])
    hist = config["history"]
    age, gap = float(hist["seconds"]), float(config["gap_s"])
    rate = key_rates(config, float(hist["events_per_s"]))
    later = rng.poisson(rate * age)
    last = np.where(later > 0, np.maximum(age - rng.exponential(1.0 / rate),
                                          0.0), 0.0)
    vals = np.zeros((k, words), np.int32)
    vals[:, 0] = np.floor(last).astype(np.int32)
    vals[:, 1] = np.minimum(1 + rng.poisson(rate * min(gap, age)), 1 + later)
    return np.arange(k, dtype=np.int64), 1 + later.astype(np.int64), vals


class Reference:
    """``weights`` as in ``tumbling_window_join.Reference``."""

    def __init__(self, config: dict, initial: tuple, weights: str = "exact"):
        self.gap_s = float(config["gap_s"])
        self.weights = weights
        k = int(config["keyspace"])
        words = int(config["payload_words"])
        self.present = np.zeros(k, bool)
        self.weight = np.zeros(k, np.int64)
        self.vals = np.zeros((k, words), np.int32)
        keys, w, v = initial
        self.present[keys] = True
        self.weight[keys] = w
        self.vals[keys] = v

    def process(self, key, value, ts, kind):
        vals = self.vals[key]
        found = self.present[key]
        expired = (ts - vals[:, 0].astype(np.float64)) > self.gap_s
        out = np.flatnonzero(expired & found)
        vals[:, 0] = np.minimum(ts, TS_CAP).astype(np.int32)
        vals[:, 1] = np.where(expired, 1, vals[:, 1] + 1)
        uq, last, cnt = last_occurrence(key)
        self.present[uq] = True
        self.weight[uq] += np.ones_like(cnt) if self.weights == "per_batch" \
            else cnt
        if self.weights == "bfloat16":
            self.weight[uq] = bf16_round(self.weight[uq])
        self.vals[uq] = vals[last]
        return key[out], value[out], ts[out], kind[out]

    def state(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        idx = np.flatnonzero(self.present)
        return idx.astype(np.int64), self.weight[idx], self.vals[idx]

    def must_keep(self, keys: np.ndarray) -> np.ndarray:
        return np.ones(len(keys), bool)
