"""Streaming operators.  Stateless operators never touch the state backend
(Justin strips their managed memory — Takeaway 1); stateful operators access
their per-task ``LSMStore`` with the read/write profile the paper's §3
microbenchmarks characterize:

* ``KeyedStateOp(mode="read")``   — pure lookups (Read workload)
* ``KeyedStateOp(mode="write")``  — blind writes (Write workload)
* ``KeyedStateOp(mode="update")`` — read-modify-write (Update workload)
* ``WindowAggOp`` + ``HotItemsOp`` / ``SessionWindowOp`` / ``JoinOp`` — the
  Nexmark patterns.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from repro.obs.spans import counts, span
from repro.state.lsm import LSMStore, LatencyModel
from repro.streaming.events import EventBatch, PAYLOAD_WORDS


class Operator:
    """Base: subclasses implement process(task_state, batch) -> out batch."""
    stateful = False
    cpu_cost_us = 1.0                   # per-event CPU service time component
    entry_bytes = 1000                  # logical state-entry size (§3: 1 KB)
    event_time = False                  # True: gets ``on_watermark`` calls

    def __init__(self, name: str):
        self.name = name

    def make_state(self, memory_mb: float, seed: int = 0) -> LSMStore | None:
        if not self.stateful:
            return None
        return LSMStore(memory_mb, value_words=PAYLOAD_WORDS,
                        entry_bytes=self.entry_bytes, seed=seed)

    def process(self, state: LSMStore | None, batch: EventBatch) -> EventBatch:
        raise NotImplementedError

    def on_watermark(self, state: LSMStore | None,
                     watermark: float) -> EventBatch:
        """Every event with a timestamp below ``watermark`` that this
        task will receive has been processed (the engine calls this for
        each task after its turn in a tick): fire what that completes."""
        return EventBatch.empty()

    def warm_state(self, state: LSMStore, rng: np.random.Generator) -> None:
        """Optional pre-population (paper §3 pre-populates every key)."""


class SourceOp(Operator):
    """Workload injector: emits up to ``rate`` events/s, subject to
    backpressure (paper: sources are excluded from the resource count)."""
    cpu_cost_us = 0.2

    def __init__(self, name: str, generator: Callable[[int, float], EventBatch]):
        super().__init__(name)
        self.generator = generator

    def emit(self, n: int, now_s: float) -> EventBatch:
        return self.generator(n, now_s)

    def process(self, state, batch):
        return batch


class MapOp(Operator):
    def __init__(self, name: str, fn: Callable[[EventBatch], EventBatch],
                 cpu_cost_us: float = 1.2):
        super().__init__(name)
        self.fn = fn
        self.cpu_cost_us = cpu_cost_us

    def process(self, state, batch):
        return self.fn(batch)


class FilterOp(Operator):
    def __init__(self, name: str, pred: Callable[[EventBatch], np.ndarray],
                 cpu_cost_us: float = 0.8):
        super().__init__(name)
        self.pred = pred
        self.cpu_cost_us = cpu_cost_us

    def process(self, state, batch):
        return batch.select(self.pred(batch))


class FlatMapOp(Operator):
    def __init__(self, name: str, fn: Callable[[EventBatch], EventBatch],
                 cpu_cost_us: float = 1.5):
        super().__init__(name)
        self.fn = fn
        self.cpu_cost_us = cpu_cost_us

    def process(self, state, batch):
        return self.fn(batch)


class SinkOp(Operator):
    cpu_cost_us = 0.5

    def __init__(self, name: str = "sink"):
        super().__init__(name)
        self.received = 0

    def process(self, state, batch):
        self.received += len(batch)
        return EventBatch.empty()


@dataclass
class _StateProfile:
    keyspace: int = 1_000_000
    prepopulate: bool = True


class KeyedStateOp(Operator):
    """§3 microbenchmark operator: one state access per event."""
    stateful = True
    cpu_cost_us = 2.0

    def __init__(self, name: str, mode: str, keyspace: int = 1_000_000,
                 prepopulate: bool = True):
        super().__init__(name)
        assert mode in ("read", "write", "update")
        self.mode = mode
        self.keyspace = keyspace
        self.prepopulate = prepopulate

    def warm_state(self, state: LSMStore, rng: np.random.Generator) -> None:
        if not self.prepopulate:
            return
        keys = np.arange(self.keyspace, dtype=np.int64)
        vals = rng.integers(0, 2**31 - 1, (self.keyspace, PAYLOAD_WORDS),
                            dtype=np.int64).astype(np.int32)
        state.bulk_load(keys, vals)
        state.metrics.reset()

    def process(self, state: LSMStore, batch: EventBatch) -> EventBatch:
        if self.mode == "read":
            vals, _ = state.get_batch(batch.key)
            out = batch.value + vals[:, :batch.value.shape[1]]
            return EventBatch(batch.key, out.astype(np.int32), batch.ts,
                              batch.kind)
        if self.mode == "write":
            state.put_batch(batch.key, batch.value)
            return batch
        vals, _ = state.get_batch(batch.key)           # update = read + write
        new = (vals + batch.value).astype(np.int32)
        state.put_batch(batch.key, new)
        return EventBatch(batch.key, new, batch.ts, batch.kind)


WINDOW_BITS = 20                        # window state key: key << 20 | window
WINDOW_MASK = np.int64((1 << WINDOW_BITS) - 1)
KEY_LIMIT = np.int64(1) << np.int64(63 - WINDOW_BITS)


def window_key(keys: np.ndarray, wid: np.ndarray) -> np.ndarray:
    """State key of (event key, window id): the event key above the low
    ``WINDOW_BITS`` bits, the window id modulo ``2**WINDOW_BITS`` in
    them."""
    keys = np.asarray(keys, np.int64)
    if len(keys) and (keys.min() < 0 or keys.max() >= KEY_LIMIT):
        raise ValueError("event key outside [0, 2**43)")
    return (keys << WINDOW_BITS) | (wid & WINDOW_MASK)


class WindowAggOp(Operator):
    """Hopping-window count per key (NEXmark q5, Flink's
    ``HOP(size, slide)``), with a combiner for the per-window maximum.

    Windows are ``[k*slide, k*slide + size)``; a window's id is the index
    of its end in slides (``k + size/slide``), so an event at ``ts`` lies
    in the ``size/slide`` windows with ids ``floor(ts/slide) + 1`` to
    ``floor(ts/slide) + size/slide``.  State key = (key, window id)
    (``window_key``), word 0 of the value the window's count.  A batch
    is counted with one ``np.unique`` over its (key, window) pairs, then
    read and written back in one ``get_batch`` and one ``put_batch``.

    Output (the combiner): for each window the batch touched, the keys
    whose new count equals the batch's largest for that window, as rows
    keyed by window id with value ``[key, count, 0, 0]``, the batch's
    earliest timestamp and kind 0.  Counts only grow and a key reaches
    each count once, so the keys at a window's final maximum are all
    among these rows, each once: ``HotItemsOp`` finds the maximum, its
    lowest key and the number of keys at it from them exactly.

    Event time: when the engine's watermark passes a window's end, the
    window's counts are deleted (``LSMStore.purge``)."""
    stateful = True
    event_time = True
    cpu_cost_us = 2.5
    entry_bytes = 500                    # window aggregates are small records

    def __init__(self, name: str, size_s: float, slide_s: float):
        super().__init__(name)
        n = size_s / slide_s
        if n != round(n) or n < 1:
            raise ValueError(f"window size {size_s} s is not a multiple "
                             f"of its slide {slide_s} s")
        self.size_s = size_s
        self.slide_s = slide_s
        self.windows_per_event = int(round(n))

    def process(self, state: LSMStore, batch: EventBatch) -> EventBatch:
        if len(batch) == 0:
            return EventBatch.empty()
        with span("hop.assign"):
            first = np.floor(batch.ts / self.slide_s).astype(np.int64) + 1
            wid = first[:, None] + np.arange(self.windows_per_event)
            uq, cnt = np.unique(window_key(batch.key[:, None], wid),
                                return_counts=True)
        vals, _ = state.get_batch(uq, uhint=(uq, np.ones_like(cnt)))
        vals[:, 0] += cnt.astype(np.int32)
        state.put_batch(uq, vals)
        counts["hop.updates"] += len(uq)
        with span("hop.combine"):
            win = uq & WINDOW_MASK
            c = vals[:, 0]
            lo = win.min()
            best = np.zeros(int(win.max() - lo) + 1, np.int32)
            np.maximum.at(best, win - lo, c)
            top = np.flatnonzero(c == best[win - lo])
            value = np.zeros((len(top), vals.shape[1]), np.int32)
            value[:, 0] = uq[top] >> WINDOW_BITS
            value[:, 1] = c[top]
            return EventBatch(win[top], value,
                              np.full(len(top), batch.ts.min()),
                              np.zeros(len(top), np.int8))

    def on_watermark(self, state: LSMStore, watermark: float) -> EventBatch:
        with span("hop.expire"):
            closed = int(np.floor(watermark / self.slide_s))
            state.purge(lambda keys: (keys & WINDOW_MASK) > closed)
        return EventBatch.empty()


class HotItemsOp(Operator):
    """The hot item of each window (NEXmark q5's second stage), from the
    combiner rows of ``WindowAggOp``: the largest count, the lowest key
    at it and the number of keys at it.

    Keyed by window id, so any parallelism is exact.  State per window:
    ``[max count, lowest key at max, keys at max, 0]``.  When the
    engine's watermark passes a window's end (id x ``slide_s``), its row
    ``[key, count, ties, 0]`` is emitted, keyed by window id and stamped
    with the window's end (kind 0), and its state deleted.  ``q5.sql``
    emits one row per tied key; here ties are the count beside the
    lowest key."""
    stateful = True
    event_time = True
    cpu_cost_us = 1.0
    entry_bytes = 500

    def __init__(self, name: str, slide_s: float):
        super().__init__(name)
        self.slide_s = slide_s

    def process(self, state: LSMStore, batch: EventBatch) -> EventBatch:
        if len(batch) == 0:
            return EventBatch.empty()
        with span("hop.fire"):
            key = batch.value[:, 0].astype(np.int64)
            cnt = batch.value[:, 1]
            order = np.lexsort((key, -cnt.astype(np.int64), batch.key))
            win, key, cnt = batch.key[order], key[order], cnt[order]
            starts = np.flatnonzero(np.r_[True, win[1:] != win[:-1]])
            m, low = cnt[starts], key[starts]
            at_max = cnt == np.repeat(m, np.diff(np.r_[starts, len(win)]))
            ties = np.add.reduceat(at_max.astype(np.int32), starts)
            uw = win[starts]
            vals, found = state.get_batch(uw)
            old_m, old_low, old_ties = vals[:, 0], vals[:, 1], vals[:, 2]
            up = ~found | (m > old_m)
            eq = found & (m == old_m)
            vals[:, 0] = np.where(up, m, old_m)
            vals[:, 1] = np.where(up, low, np.where(
                eq, np.minimum(low, old_low), old_low))
            vals[:, 2] = np.where(up, ties, old_ties + np.where(eq, ties, 0))
            state.put_batch(uw, vals)
        return EventBatch.empty()

    def on_watermark(self, state: LSMStore, watermark: float) -> EventBatch:
        with span("hop.fire"):
            closed = int(np.floor(watermark / self.slide_s))
            keys, vals = state.items()
            due = keys <= closed
            counts["hop.fired"] += int(due.sum())
            if not due.any():
                return EventBatch.empty()
            state.purge(lambda k: k > closed)
            value = np.zeros_like(vals[due])
            value[:, 0], value[:, 1], value[:, 2] = \
                vals[due, 1], vals[due, 0], vals[due, 2]
            return EventBatch(keys[due], value, keys[due] * self.slide_s,
                              np.zeros(len(value), np.int8))


class SessionWindowOp(Operator):
    """q11: per-user session tracking — update-heavy, working set = active
    users (the memory-pressured operator where Justin's scale-up wins)."""
    stateful = True
    cpu_cost_us = 3.0
    entry_bytes = 500                    # session records are small

    def __init__(self, name: str, gap_s: float = 10.0,
                 keyspace: int = 1_000_000):
        super().__init__(name)
        self.gap_s = gap_s
        self.keyspace = keyspace

    def warm_state(self, state: LSMStore, rng: np.random.Generator) -> None:
        state.bulk_load(np.arange(self.keyspace, dtype=np.int64),
                        np.zeros((self.keyspace, PAYLOAD_WORDS), np.int32))
        state.metrics.reset()

    def process(self, state: LSMStore, batch: EventBatch) -> EventBatch:
        if len(batch) == 0:
            return EventBatch.empty()
        vals, found = state.get_batch(batch.key)
        last_ts = vals[:, 0].astype(np.float64)
        expired = (batch.ts - last_ts) > self.gap_s
        emitted = batch.select(expired & found)          # closed sessions
        vals[:, 0] = np.minimum(batch.ts, 2**30).astype(np.int32)
        vals[:, 1] = np.where(expired, 1, vals[:, 1] + 1)  # bids in session
        state.put_batch(batch.key, vals)
        return emitted


class JoinOp(Operator):
    """Two-sided keyed join.  Events with kind==left_kind are stored and
    probe the right side (and vice versa).  ``windowed=True`` scopes state
    keys by tumbling window id (q8); otherwise the join is incremental and
    unbounded (q3)."""
    stateful = True
    cpu_cost_us = 3.0
    entry_bytes = 500                    # join-side records are small

    def __init__(self, name: str, left_kind: int, right_kind: int,
                 window_s: float | None = None, keyspace: int = 0):
        super().__init__(name)
        self.left_kind = left_kind
        self.right_kind = right_kind
        self.window_s = window_s
        self.keyspace = keyspace         # pre-populated steady-state size

    def warm_state(self, state, rng: np.random.Generator) -> None:
        """Steady-state pre-population: both sides of the live window(s) —
        the paper's queries run for minutes before each decision window."""
        if not self.keyspace:
            return
        wids = (0, 1) if self.window_s is not None else (None,)
        all_keys, all_vals = [], []
        for side in (0, 1):
            for wid in wids:
                keys = np.arange(self.keyspace, dtype=np.int64) * 4 + side
                if wid is not None:
                    keys = keys * np.int64(1 << 16) + wid
                vals = rng.integers(0, 2**31 - 1,
                                    (self.keyspace, PAYLOAD_WORDS),
                                    dtype=np.int64).astype(np.int32)
                all_keys.append(keys)
                all_vals.append(vals)
        state.bulk_load(np.concatenate(all_keys), np.concatenate(all_vals))
        state.metrics.reset()

    def _skey(self, keys, ts, side: int) -> np.ndarray:
        k = keys * np.int64(4) + side
        if self.window_s is not None:
            wid = (ts // self.window_s).astype(np.int64)
            k = k * np.int64(1 << 16) + (wid % (1 << 16))
        return k

    def process(self, state: LSMStore, batch: EventBatch) -> EventBatch:
        if len(batch) == 0:
            return EventBatch.empty()
        if self.window_s is not None:
            wm = int(batch.ts.max() // self.window_s)
            state.compact_filter = \
                lambda keys, w=wm: (keys % (1 << 16)) >= max(0, w - 2)
        left = batch.kind == self.left_kind
        right = batch.kind == self.right_kind
        out = []
        for mask, mine, other in ((left, 0, 1), (right, 1, 0)):
            if not mask.any():
                continue
            sub = batch.select(mask)
            d = state.put_batch(self._skey(sub.key, sub.ts, mine), sub.value)
            if d is not None:
                # probe keys are the put keys shifted by a constant (the
                # side bit is below the window bits), so the put batch's
                # delta decomposition doubles as the probe's sorted-unique
                # hint — one sort serves both Z-set operations
                shift = np.int64((other - mine)
                                 * ((1 << 16) if self.window_s is not None
                                    else 1))
                vals, found = state.get_batch(
                    self._skey(sub.key, sub.ts, other),
                    uhint=(d[0] + shift, d[1]))
            else:
                vals, found = state.get_batch(
                    self._skey(sub.key, sub.ts, other))
            if found.any():
                joined = sub.select(found)
                out.append(EventBatch(joined.key, vals[found], joined.ts,
                                      joined.kind))
        return EventBatch.concat(out) if out else EventBatch.empty()
