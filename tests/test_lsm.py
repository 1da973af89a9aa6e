"""LSM state backend: correctness vs a dict oracle + invariants."""
import numpy as np
import pytest

# hypothesis is an optional [test] extra: only the property test needs it
# (with a pinned-seed fallback below).  A module-level importorskip here
# used to silently skip the WHOLE file — tools/check_collect.py now guards
# against that regressing.
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.state.lsm import LSMStore, LatencyModel


def make_store(mb=8.0, **kw):
    return LSMStore(mb, value_words=2, **kw)


def test_put_get_roundtrip(rng):
    s = make_store()
    keys = rng.choice(10_000, 500, replace=False).astype(np.int64)
    vals = rng.integers(0, 1 << 30, (500, 2)).astype(np.int32)
    s.put_batch(keys, vals)
    got, found = s.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(got, vals)


def test_absent_keys_not_found(rng):
    s = make_store()
    s.put_batch(np.arange(100, dtype=np.int64),
                np.ones((100, 2), np.int32))
    got, found = s.get_batch(np.arange(200, 300).astype(np.int64))
    assert not found.any()


def test_overwrite_last_wins(rng):
    s = make_store()
    keys = np.arange(50, dtype=np.int64)
    s.put_batch(keys, np.full((50, 2), 1, np.int32))
    s.put_batch(keys, np.full((50, 2), 2, np.int32))
    got, found = s.get_batch(keys)
    assert found.all()
    assert (got == 2).all()


def test_flush_and_compaction_preserve_data(rng):
    s = LSMStore(0.5, value_words=2)           # tiny memtable: many flushes
    oracle = {}
    for _ in range(10):
        keys = rng.integers(0, 5_000, 1_000).astype(np.int64)
        vals = rng.integers(0, 1 << 30, (1_000, 2)).astype(np.int32)
        # dedupe within batch the same way the store does (last wins)
        s.put_batch(keys, vals)
        for k, v in zip(keys, vals):
            oracle[int(k)] = v
    assert s.metrics.flushes > 0
    probe = np.array(sorted(oracle), np.int64)
    got, found = s.get_batch(probe)
    assert found.all()
    expect = np.stack([oracle[int(k)] for k in probe])
    np.testing.assert_array_equal(got, expect)


def test_resize_preserves_data(rng):
    s = make_store(4.0)
    keys = np.arange(2_000, dtype=np.int64)
    vals = rng.integers(0, 100, (2_000, 2)).astype(np.int32)
    s.put_batch(keys, vals)
    s.resize(16.0)
    got, found = s.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(got, vals)
    assert s.memory_mb == 16.0


def test_snapshot_restore(rng):
    s = make_store()
    keys = rng.choice(100_000, 3_000, replace=False).astype(np.int64)
    vals = rng.integers(0, 1 << 30, (3_000, 2)).astype(np.int32)
    s.put_batch(keys, vals)
    snap = s.snapshot()
    s2 = LSMStore.restore(snap)
    got, found = s2.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(got, vals)


def test_memory_layout_paper_rules():
    """§3: memtable <= 64 MB and at least half the budget goes to cache."""
    s128 = LSMStore(128)
    assert s128.memtable_cap == 32 * 1024 * 1024 // 1000   # 32 MB memtable
    s256 = LSMStore(256)
    assert s256.memtable_cap == 64 * 1024 * 1024 // 1000   # 64 MB memtable
    s1024 = LSMStore(1024)
    assert s1024.memtable_cap == s256.memtable_cap          # capped at 64 MB


def test_compact_filter_drops_entries(rng):
    s = LSMStore(0.5, value_words=2)
    s.compact_filter = lambda keys: keys >= 500
    s.put_batch(np.arange(1_000, dtype=np.int64),
                np.ones((1_000, 2), np.int32))
    for _ in range(5):                          # force flush+compaction
        s.put_batch(np.arange(1_000, 2_000, dtype=np.int64),
                    np.ones((1_000, 2), np.int32))
    s._flush()
    keys, _ = s.items()
    assert (keys >= 500).all()


@pytest.mark.parametrize("impl", ["numpy", "interpret"])
def test_purge_leaves_no_older_version(rng, impl):
    """Keys written to a level, then again to memtable runs and read
    into the cache: ``purge`` removes every version of the rejected keys
    at once (a compaction filter on the next flush would drop only the
    newest, and the level's version would be read again)."""
    s = LSMStore(0.5, value_words=2, kernel_impl=impl)
    keys = np.arange(300, dtype=np.int64)
    s.put_batch(keys, np.ones((300, 2), np.int32))
    s._flush()
    for i in range(3):                          # delta runs over the level
        s.put_batch(keys[::2], np.full((150, 2), 2 + i, np.int32))
    s.get_batch(keys)                           # level reads fill the cache
    w0 = s.total_weight()
    s.purge(lambda k: k >= 100)
    vals, found = s.get_batch(keys)
    assert not found[:100].any() and found[100:].all()
    np.testing.assert_array_equal(vals[100:, 0],
                                  np.where(keys[100:] % 2, 1, 4))
    got, _ = s.items()
    np.testing.assert_array_equal(got, keys[100:])
    assert s.annihilated == w0 - s.total_weight() == 100 + 3 * 50
    assert not np.isin(s.cache_keys, keys[:100]).any()


def test_cache_hit_rate_increases_with_memory(rng):
    """Takeaway 2: bigger cache => higher read hit rate (uniform reads)."""
    rates = []
    for mb in (2, 8, 32):
        s = LSMStore(mb, value_words=2)
        keys = np.arange(20_000, dtype=np.int64)
        vals = np.zeros((20_000, 2), np.int32)
        s.put_batch(keys, vals)
        s.prewarm_cache(keys, vals)
        for _ in range(5):
            s.get_batch(rng.integers(0, 20_000, 2_000).astype(np.int64))
        rates.append(s.metrics.cache_hit_rate)
    assert rates[0] < rates[1] < rates[2] or rates[2] > 0.95


def test_write_latency_insensitive_to_cache(rng):
    """Takeaway 3: cache size does not affect write cost."""
    taus = []
    for mb in (128, 1024):
        s = LSMStore(mb)
        keys = rng.integers(0, 1 << 20, 20_000).astype(np.int64)
        vals = np.zeros((20_000, 4), np.int32)
        s.put_batch(keys, vals)
        taus.append(s.metrics.access_latency_total_ms / 20_000)
    assert abs(taus[0] - taus[1]) / max(taus[0], taus[1]) < 0.5


def _check_store_matches_dict(ops):
    """Property body: LSM == python dict under any put sequence (last wins)."""
    s = LSMStore(0.25, value_words=1)           # tiny: exercises flush paths
    oracle = {}
    keys = np.array([k for k, _ in ops], np.int64)
    vals = np.array([[v] for _, v in ops], np.int32)
    for i in range(0, len(ops), 37):            # uneven batches
        s.put_batch(keys[i:i + 37], vals[i:i + 37])
        for k, v in zip(keys[i:i + 37], vals[i:i + 37]):
            oracle[int(k)] = int(v[0])
    probe = np.array(sorted(oracle), np.int64)
    got, found = s.get_batch(probe)
    assert found.all()
    assert [int(x) for x in got[:, 0]] == [oracle[int(k)] for k in probe]


if HAS_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 999), st.integers(0, 2**20)),
                    min_size=1, max_size=300))
    def test_property_store_matches_dict(ops):
        _check_store_matches_dict(ops)
else:
    @pytest.mark.parametrize("seed", range(25))
    def test_property_store_matches_dict(seed):
        r = np.random.default_rng(seed)
        m = int(r.integers(1, 300))
        ops = list(zip(r.integers(0, 1000, m).tolist(),
                       r.integers(0, 1 << 20, m).tolist()))
        _check_store_matches_dict(ops)

