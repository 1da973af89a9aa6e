"""NEXmark q5, hot items, as the Flink NEXmark suite's ``q5.sql`` runs it:
bids counted per auction in hopping windows of 10 s every 2 s, then the
auction with the most bids in each window once the watermark passes its
end.  The engine's q5 is compared with a plain reference computed from
the bids alone: dense counts per (window, auction), no store, no
partitioning, no combiner."""
import numpy as np
import pytest

from repro.data.nexmark import BID, QUERIES
from repro.obs.spans import counts
from repro.streaming.engine import StreamEngine
from repro.streaming.events import PAYLOAD_WORDS, EventBatch
from repro.streaming.operators import WINDOW_BITS

SIZE, SLIDE = 10.0, 2.0
AUCTIONS = 2_000


class Bids:
    """Seeded bids on ``AUCTIONS`` auctions (auction id in word 2),
    stamped with the tick's time; keeps every (auction, ts) it emits."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed)
        self.auction, self.ts = [], []

    def __call__(self, n: int, now: float) -> EventBatch:
        auction = self.rng.integers(0, AUCTIONS, n)
        value = np.zeros((n, PAYLOAD_WORDS), np.int32)
        value[:, 2] = auction
        ts = np.full(n, float(now))
        self.auction.append(auction)
        self.ts.append(ts)
        return EventBatch(self.rng.integers(0, 1_000, n), value, ts,
                          np.full(n, BID, np.int8))


def reference_counts(auction: np.ndarray, ts: np.ndarray) -> dict:
    """Window id (its end in slides) -> bids per auction, from the window
    definition alone: window ``e`` is ``[e*SLIDE - SIZE, e*SLIDE)``."""
    counts = {}
    for e in range(int(ts.min() // SLIDE),
                   int((ts.max() + SIZE) // SLIDE) + 2):
        inside = (e * SLIDE - SIZE <= ts) & (ts < e * SLIDE)
        if inside.any():
            counts[e] = np.bincount(auction[inside], minlength=AUCTIONS)
    return counts


def hot_item(c: np.ndarray) -> list:
    """[lowest auction at the max, the max, auctions at the max]."""
    m = c.max()
    return [int(np.flatnonzero(c == m)[0]), int(m), int((c == m).sum())]


class Q5:
    """The engine's q5 on seeded bids, with every ``hot_items`` emission
    and the watermark it came with."""

    def __init__(self, p: int = 3, p_hot: int = 1, seed: int = 0):
        flow = QUERIES["q5"]()
        flow.nodes["hot_auctions"].parallelism = p
        flow.nodes["hot_items"].parallelism = p_hot
        self.bids = Bids(seed)
        flow.nodes["source"].op.generator = self.bids
        self.engine = StreamEngine(flow, seed=seed)
        self.fired = []                  # (watermark, window, row)
        op = flow.nodes["hot_items"].op
        fire = op.on_watermark

        def record(state, watermark):
            out = fire(state, watermark)
            self.fired += [(watermark, int(k), v[:3].tolist())
                           for k, v in zip(out.key, out.value)]
            return out
        op.on_watermark = record

    def tick(self, rate: float, n: int = 1) -> None:
        for _ in range(n):
            self.engine.run_tick(rate)

    def drain(self) -> None:
        """Ticks without new bids until every queue is empty, then one
        more so the last watermark reaches ``hot_items``."""
        while any(t.queue for ts in self.engine.tasks.values() for t in ts):
            self.tick(0)
        self.tick(0, 2)

    def reference(self) -> dict:
        return reference_counts(np.concatenate(self.bids.auction),
                                np.concatenate(self.bids.ts))

    def live_counts(self) -> dict:
        """(window, auction) -> count over every hot_auctions task."""
        out = {}
        for t in self.engine.tasks["hot_auctions"]:
            keys, vals = t.state.items()
            for k, c in zip(keys, vals[:, 0]):
                out[(int(k & ((1 << WINDOW_BITS) - 1)),
                     int(k >> WINDOW_BITS))] = int(c)
        return out


def assert_matches_reference(q: Q5) -> None:
    ref = q.reference()
    fired = {}
    for _, e, row in q.fired:
        assert e not in fired, f"window {e} emitted twice"
        fired[e] = row
    closed = {e for e in ref if e * SLIDE <= q.engine.now}
    assert set(fired) == closed and closed
    for e in closed:
        assert fired[e] == hot_item(ref[e]), e
    live = q.live_counts()
    for (e, a), c in live.items():
        assert c == ref[e][a], (e, a)
    for e, c in ref.items():                   # open windows are all kept
        if e * SLIDE > q.engine.now:
            for a in np.flatnonzero(c):
                assert live[(e, int(a))] == c[a]


@pytest.mark.parametrize("case", ["p3", "hot_items_p2", "rescaled"])
def test_q5_matches_the_reference(case):
    before = counts.copy()
    q = Q5(p=3, p_hot=2 if case == "hot_items_p2" else 1, seed=11)
    q.tick(3_000, 5)
    if case == "rescaled":             # counts survive re-partition
        q.drain()
        q.engine.reconfigure({"hot_auctions": (2, 1), "hot_items": (2, 0)})
        q.tick(3_000, 4)
    q.drain()
    assert_matches_reference(q)
    # the counters count with spans off
    got = counts - before
    assert got["hop.fired"] == len(q.fired) and got["hop.updates"] > 0


def _window_agg():
    op = QUERIES["q5"]().nodes["hot_auctions"].op
    return op, op.make_state(158.0)


def _bids(auctions, ts) -> EventBatch:
    auctions = np.asarray(auctions, np.int64)
    return EventBatch(auctions, np.zeros((len(auctions), PAYLOAD_WORDS),
                                         np.int32),
                      np.full(len(auctions), float(ts)),
                      np.full(len(auctions), BID, np.int8))


def _windows(state) -> dict:
    keys, vals = state.items()
    return {(int(k >> WINDOW_BITS), int(k & ((1 << WINDOW_BITS) - 1))):
            int(c) for k, c in zip(keys, vals[:, 0])}


def test_every_duplicate_in_a_batch_is_counted():
    op, state = _window_agg()
    out = op.process(state, _bids([7] * 12 + [9] * 3, 0.0))
    got = _windows(state)
    assert {c for (a, _), c in got.items() if a == 7} == {12}
    assert {c for (a, _), c in got.items() if a == 9} == {3}
    # the combiner forwards only the batch's max per window
    assert set(out.value[:, 0]) == {7} and set(out.value[:, 1]) == {12}


def test_a_bid_lies_in_exactly_its_windows():
    op, state = _window_agg()
    op.process(state, _bids([5], 13.0))
    ends = sorted(w * SLIDE for (_, w) in _windows(state))
    assert [(e - SIZE, e) for e in ends] == [(4, 14), (6, 16), (8, 18),
                                              (10, 20), (12, 22)]


def test_hot_items_breaks_ties_at_the_lowest_auction():
    op = QUERIES["q5"]().nodes["hot_items"].op
    state = op.make_state(158.0)

    def rows(pairs):
        win = np.array([w for w, _, _ in pairs], np.int64)
        v = np.zeros((len(pairs), PAYLOAD_WORDS), np.int32)
        v[:, 0] = [a for _, a, _ in pairs]
        v[:, 1] = [c for _, _, c in pairs]
        return EventBatch(win, v, np.zeros(len(pairs)),
                          np.zeros(len(pairs), np.int8))

    op.process(state, rows([(5, 7, 4), (5, 9, 2), (6, 1, 1)]))
    op.process(state, rows([(5, 3, 4), (6, 8, 2)]))
    op.process(state, rows([(6, 11, 5), (6, 2, 5)]))
    assert len(op.on_watermark(state, 9.9)) == 0
    out = op.on_watermark(state, 10.0)           # window 5 ends at 10 s
    assert out.key.tolist() == [5] and out.ts.tolist() == [10.0]
    assert out.value[0, :3].tolist() == [3, 4, 2]
    assert len(op.on_watermark(state, 11.0)) == 0        # emitted once
    out = op.on_watermark(state, 12.0)
    assert out.key.tolist() == [6] and out.value[0, :3].tolist() == [2, 5, 2]


def test_hot_items_waits_for_every_upstream_task():
    """One hot_auctions task held back: no window closes while that task
    still holds bids of it, and once it catches up every bid is
    counted."""
    q = Q5(p=3, seed=5)
    q.tick(9_000, 2)
    q.engine.set_straggler("hot_auctions", 0, 1e4)
    held = False
    for _ in range(6):
        q.tick(9_000)
        behind = min((float(b.ts.min())
                      for b in q.engine.tasks["hot_auctions"][0].queue),
                     default=q.engine.now)
        assert all(e * SLIDE <= behind for _, e, _ in q.fired)
        held |= behind + SLIDE <= q.engine.now
    assert held                  # the straggler fell a window behind
    q.engine.set_straggler("hot_auctions", 0, 1.0)
    q.drain()
    assert_matches_reference(q)
