"""Fast-path invariants (engine.py module docstring): the coalesced
processing path and the optimized LSM internals must preserve determinism,
snapshot/restore and reconfigure semantics, and stay bit-identical to the
reference (sequential) CLOCK cache.
"""
import copy

import numpy as np
import pytest

from repro.data.nexmark import BidGen
from repro.state.lsm import LSMStore
from repro.streaming.engine import StreamEngine, state_partition_keys
from repro.streaming.events import PAYLOAD_WORDS, hash_partition
from repro.streaming.graph import Dataflow
from repro.streaming.operators import JoinOp, KeyedStateOp, SinkOp, SourceOp


def pressured_flow(p=1, keyspace=50_000):
    """Update-heavy stateful flow driven hard enough that per-tick takes
    coalesce thousands of events per process call."""
    f = Dataflow("t")
    op = KeyedStateOp("agg", "update", keyspace=keyspace, prepopulate=False)
    f.chain(SourceOp("source", BidGen(seed=1)), op, SinkOp("sink"))
    f.nodes["agg"].parallelism = p
    return f


def task_items(eng, name):
    return [t.state.items() for t in eng.tasks[name]]


# ------------------------------------------------------------ determinism
def test_coalesced_run_is_deterministic():
    """Two engines, same seed, same drive -> identical metrics + state."""
    runs = []
    for _ in range(2):
        eng = StreamEngine(pressured_flow(p=2), seed=7)
        eng.run(8, 40_000)
        m = eng.collect()
        items = task_items(eng, "agg")
        runs.append((m, items))
    m0, m1 = runs[0][0], runs[1][0]
    assert m0 == m1
    for (k0, v0), (k1, v1) in zip(runs[0][1], runs[1][1]):
        np.testing.assert_array_equal(k0, k1)
        np.testing.assert_array_equal(v0, v1)


def test_snapshot_restore_under_coalesced_path():
    """snapshot()/restore() round-trips state contents exactly, and the
    restored engine replays identically (epoch-barrier semantics)."""
    eng = StreamEngine(pressured_flow(p=2), seed=3)
    eng.run(6, 40_000)
    snap = eng.snapshot()
    before = task_items(eng, "agg")

    eng.run(6, 40_000)                       # diverge
    eng.restore(snap)
    after = task_items(eng, "agg")
    for (k0, v0), (k1, v1) in zip(before, after):
        np.testing.assert_array_equal(k0, k1)
        np.testing.assert_array_equal(v0, v1)
    assert eng.now == snap["now"]
    # (replay equality across restores is NOT asserted: the source
    # generator's rng is deliberately outside the epoch snapshot, as on
    # the seed engine, so a replay sees fresh events)
    eng.run(4, 40_000)                       # restored engine keeps running
    assert eng.collect()["agg"]["processed"] > 0


def test_reconfigure_preserves_state_contents():
    """Scale out + memory level change re-partitions every live entry."""
    eng = StreamEngine(pressured_flow(p=2), seed=3)
    eng.run(6, 40_000)
    merged = {}
    for k, v in task_items(eng, "agg"):
        merged.update(zip(k.tolist(), map(tuple, v.tolist())))
    eng.reconfigure({"agg": (5, 1)})
    merged_after = {}
    for k, v in task_items(eng, "agg"):
        merged_after.update(zip(k.tolist(), map(tuple, v.tolist())))
    assert merged == merged_after
    assert len(eng.tasks["agg"]) == 5
    eng.run(2, 40_000)                       # still processes
    assert eng.collect()["sink"]["rate_in"] > 0


# ------------------------------------------------ partition installer
def _snapshot_sources(rng, kind, n_sources):
    """State snapshots to re-partition: each source key-sorted and unique
    (what ``LSMStore.snapshot`` returns), all drawn from one key pool so
    keys repeat across sources.  Join keys carry the side and a tumbling
    window id in their low bits, as ``JoinOp._skey`` builds them."""
    sources = []
    for _ in range(n_sources):
        n = 24_000 // n_sources
        if kind == "join":
            ek = rng.integers(0, 6_000, n).astype(np.int64)
            keys = (ek * 4 + rng.integers(0, 2, n)) * np.int64(1 << 16) \
                + rng.integers(0, 3, n)
        else:
            keys = rng.integers(0, 40_000, n).astype(np.int64)
        keys = np.unique(keys)
        m = len(keys)
        sources.append({
            "keys": keys,
            "vals": rng.integers(0, 2**31 - 1, (m, PAYLOAD_WORDS),
                                 dtype=np.int64).astype(np.int32),
            "weights": rng.integers(1, 4, m).astype(np.int64)})
    return sources


def reference_install(op, sources, memory_mb, rng):
    """The plain installer: one global stable sort by (partition, key) of
    the concatenated sources, in source order, carrying the weights; each
    task's run goes into a fresh store, whose cache is then prewarmed in
    arrival order, tasks in order, from the shared rng."""
    keys = np.concatenate([s["keys"] for s in sources])
    vals = np.concatenate([s["vals"] for s in sources])
    weights = np.concatenate([s["weights"] for s in sources])
    part = hash_partition(state_partition_keys(op, keys), len(memory_mb))
    order = np.lexsort((keys, part))
    stores = []
    for i, mb in enumerate(memory_mb):
        st = LSMStore(mb, value_words=PAYLOAD_WORDS,
                      entry_bytes=op.entry_bytes, seed=i)
        run = order[part[order] == i]
        if len(run):
            st.install_run(keys[run], vals[run], weights[run])
            arrival = np.sort(run)
            st.prewarm_cache(keys[arrival], vals[arrival], rng)
        st.metrics.reset()
        stores.append(st)
    return stores


@pytest.mark.parametrize("kind,p", [("join", 3), ("join", 9),
                                    ("keyed", 1), ("keyed", 3)])
def test_install_partitions_matches_plain_reference(kind, p):
    """``StreamEngine._install_partitions`` (the rescale and warm-up
    path) equals the plain reference: every task's run contents and
    weights, its CLOCK cache, and the shared rng's position after the
    prewarm draws."""
    for n_sources in (1, 3):
        op = (JoinOp("state", 0, 1, window_s=10.0) if kind == "join"
              else KeyedStateOp("state", "update", prepopulate=False))
        flow = Dataflow("install")
        flow.chain(SourceOp("source", BidGen(seed=1)), op, SinkOp("sink"))
        flow.nodes["state"].parallelism = p
        eng = StreamEngine(flow, seed=5, base_mem_mb=1.0, warm=False)
        sources = _snapshot_sources(np.random.default_rng(p * 10 + n_sources),
                                    kind, n_sources)
        rng = copy.deepcopy(eng.rng)
        eng._install_partitions("state", sources)
        want = reference_install(
            op, sources, [t.state.memory_mb for t in eng.tasks["state"]], rng)
        assert eng.rng.bit_generator.state == rng.bit_generator.state
        for i, (t, ref) in enumerate(zip(eng.tasks["state"], want)):
            tag = f"{kind} p={p} sources={n_sources} task={i}"
            got, exp = t.state.snapshot(), ref.snapshot()
            for field in ("keys", "vals", "weights"):
                np.testing.assert_array_equal(got[field], exp[field],
                                              err_msg=f"{tag} {field}")
            for attr in ("cache_keys", "cache_vals", "cache_ref",
                         "cache_hand"):
                np.testing.assert_array_equal(getattr(t.state, attr),
                                              getattr(ref, attr),
                                              err_msg=f"{tag} {attr}")


# ---------------------------------------------------- LSM micro-invariants
def reference_clock_update(store, keys, vals):
    """The seed's sequential CLOCK insert — the oracle the vectorized
    ``_cache_update`` must match bit-for-bit."""
    if len(keys) == 0:
        return
    uniq, idx = np.unique(keys[::-1], return_index=True)
    keys, vals = uniq, vals[::-1][idx]
    sets = store._sets(keys)
    match = store.cache_keys[sets] == keys[:, None]
    hit = match.any(axis=1)
    way = match.argmax(axis=1)
    store.cache_vals[sets[hit], way[hit]] = vals[hit]
    store.cache_ref[sets[hit], way[hit]] = 1
    for s, k, v in zip(sets[~hit], keys[~hit], vals[~hit]):
        hand = store.cache_hand[s]
        for _ in range(2 * store.cache_ways):
            if store.cache_ref[s, hand] == 0:
                break
            store.cache_ref[s, hand] = 0
            hand = (hand + 1) % store.cache_ways
        store.cache_keys[s, hand] = k
        store.cache_vals[s, hand] = v
        store.cache_ref[s, hand] = 1
        store.cache_hand[s] = (hand + 1) % store.cache_ways


def test_vectorized_clock_matches_sequential_reference(rng):
    a = LSMStore(2.0, value_words=2)
    b = LSMStore(2.0, value_words=2)
    for step in range(60):
        n = int(rng.integers(1, 8_000))
        keys = rng.integers(0, 30_000, n).astype(np.int64)
        vals = rng.integers(0, 1 << 30, (n, 2)).astype(np.int32)
        reference_clock_update(a, keys.copy(), vals.copy())
        b._cache_update(keys, vals)
        for attr in ("cache_keys", "cache_vals", "cache_ref", "cache_hand"):
            np.testing.assert_array_equal(getattr(a, attr),
                                          getattr(b, attr), err_msg=str(step))


def test_memtable_view_matches_dict_oracle(rng):
    """Interleaved put/get: reads must return the newest write per key."""
    s = LSMStore(4.0, value_words=2)
    oracle = {}
    for _ in range(40):
        n = int(rng.integers(1, 3_000))
        keys = rng.integers(0, 5_000, n).astype(np.int64)
        vals = rng.integers(0, 1 << 30, (n, 2)).astype(np.int32)
        s.put_batch(keys, vals)
        for k, v in zip(keys.tolist(), vals.tolist()):
            oracle[k] = v
        probe = rng.integers(0, 6_000, 500).astype(np.int64)
        got, found = s.get_batch(probe)
        for i, k in enumerate(probe.tolist()):
            if k in oracle:
                assert found[i], k
                assert got[i].tolist() == oracle[k], k
            else:
                assert not found[i], k


def test_bulk_load_equals_put_batch_content(rng):
    keys = rng.choice(100_000, 20_000, replace=False).astype(np.int64)
    vals = rng.integers(0, 1 << 30, (20_000, 2)).astype(np.int32)
    a = LSMStore(8.0, value_words=2)
    a.put_batch(keys, vals)
    b = LSMStore(8.0, value_words=2)
    b.bulk_load(keys, vals)
    ka, va = a.items()
    kb, vb = b.items()
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_array_equal(va, vb)


def test_duplicate_probes_counted_as_cache_hits():
    """In one coalesced call, later occurrences of a slow-tier-fetched key
    hit the admitted block (what chunked execution observed across chunks)."""
    s = LSMStore(8.0, value_words=2)
    keys = np.arange(1_000, dtype=np.int64)
    vals = np.ones((1_000, 2), np.int32)
    s.bulk_load(keys, vals)                   # slow tier only, cold cache
    probe = np.repeat(np.arange(100, dtype=np.int64), 3)   # 3 occurrences
    got, found = s.get_batch(probe)
    assert found.all()
    np.testing.assert_array_equal(got, np.ones((300, 2), np.int32))
    m = s.metrics
    assert m.cache_hits == 200                # the duplicate occurrences
    assert m.level_probes >= 100              # one real probe per unique key
    assert m.reads == 300


def test_items_resolves_memtable_duplicates_to_newest_write():
    """Regression (PR 4): keys written twice within one memtable must
    snapshot at their NEWEST value — exactly what a read returns.  The
    seed resolved to the oldest write, so mid-memtable snapshots (state
    re-partitioning) carried stale values for hot keys."""
    s = LSMStore(8.0, value_words=2)
    s.put_batch(np.array([5, 5, 7], np.int64),
                np.array([[1, 0], [2, 0], [3, 0]], np.int32))
    ik, iv = s.items()
    got, found = s.get_batch(ik)
    assert found.all()
    np.testing.assert_array_equal(got, iv)     # snapshot == read view
    assert iv[list(ik).index(5), 0] == 2
    # and the snapshot stays frozen across later writes
    s.put_batch(np.array([5], np.int64), np.array([[9, 0]], np.int32))
    assert iv[list(ik).index(5), 0] == 2


def test_items_memtable_still_wins_over_levels():
    s = LSMStore(0.5, value_words=2)           # tiny: force flushes
    s.put_batch(np.arange(2_000, dtype=np.int64),
                np.ones((2_000, 2), np.int32))   # spills to levels
    s.put_batch(np.array([17, 17], np.int64),
                np.array([[5, 0], [6, 0]], np.int32))     # memtable rewrite
    ik, iv = s.items()
    assert iv[list(ik).index(17), 0] == 6
    assert len(ik) == 2_000
