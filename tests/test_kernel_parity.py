"""Edge-shape parity: sorted_probe / window_agg pallas kernels vs their
numpy references, in interpret mode (no accelerator needed), plus the
columnar LSM store's kernel dispatch (``kernel_impl="interpret"``) vs its
numpy oracle path.

The shape sweep here deliberately covers what tests/test_kernels.py's
random sweeps don't pin: empty inputs, single-key tables, all-duplicate
batches, and dtype-boundary keys (0, int_max — the kernel pads tables
with the maximum key, which used to false-positive a genuine int_max
probe), plus the int64 keys the store really holds: above 2^31 (q8's
join keys reach ~2^38) and negative.
"""
import numpy as np
import pytest

from repro.kernels.device import bucket
from repro.kernels.sorted_probe.kernel import (QUERY_BLOCK, TABLE_TILE,
                                               sorted_probe)
from repro.kernels.sorted_probe.ops import probe, split_keys
from repro.kernels.window_agg import kernel as agg_kernel
from repro.kernels.window_agg.kernel import (EVENT_TILE, LANES, SEG_BLOCK,
                                             window_agg)
from repro.kernels.window_agg.ops import aggregate
from repro.obs import spans
from repro.state import lsm
from repro.state.lsm import LSMStore

INT64 = np.iinfo(np.int64)


def assert_probe_parity(table, queries):
    p1, f1 = probe(table, queries, impl="interpret")
    p2, f2 = probe(table, queries, impl="ref")
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(f1, f2)
    return p1, f1


# ------------------------------------------------------------- sorted_probe
def test_probe_empty_table():
    pos, found = assert_probe_parity(np.empty(0, np.int64),
                                     np.array([1, 2, 3], np.int64))
    assert not found.any()
    assert (pos == 0).all()


def test_probe_empty_queries():
    pos, found = assert_probe_parity(np.array([1, 2, 3], np.int64),
                                     np.empty(0, np.int64))
    assert len(pos) == 0 and len(found) == 0


def test_probe_single_key_table():
    pos, found = assert_probe_parity(np.array([42], np.int64),
                                     np.array([41, 42, 43], np.int64))
    np.testing.assert_array_equal(found, [False, True, False])
    np.testing.assert_array_equal(pos, [0, 0, 1])


def test_probe_all_duplicate_queries():
    table = np.arange(0, 1000, 7, dtype=np.int64)
    queries = np.full(2048, 700, np.int64)          # all one present key
    pos, found = assert_probe_parity(table, queries)
    assert found.all()
    assert (table[pos] == 700).all()


def test_probe_duplicate_table_entries():
    """Sorted but NOT unique table: rank = leftmost insertion point."""
    table = np.array([5, 5, 5, 9, 9], np.int64)
    pos, found = assert_probe_parity(table, np.array([5, 7, 9], np.int64))
    np.testing.assert_array_equal(pos, [0, 3, 3])
    np.testing.assert_array_equal(found, [True, False, True])


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_probe_dtype_boundaries(dtype):
    """0 and int_max as real keys AND as absent probes — the kernel pads
    its table tiles with the int64 maximum, which must not read as a
    match.  int64 keys cross to the device as two int32 words, split on
    the host, so no x64 mode is involved."""
    hi = np.iinfo(dtype).max
    table = np.array([0, 17, hi], dtype)
    pos, found = assert_probe_parity(table, np.array([0, 1, hi, hi - 1],
                                                     dtype))
    np.testing.assert_array_equal(found, [True, False, True, False])
    table_no_hi = np.array([0, 17], dtype)
    _, found = assert_probe_parity(table_no_hi, np.array([hi], dtype))
    assert not found.any()                      # padding must NOT match


def test_probe_keys_above_2_31_at_q8_scale():
    """q8 join keys are ((seller*4 + side) << 16) + window: ~2^38 at
    600,000 sellers.  Keys that differ only in the high word, or only in
    the low word's top bit, must rank exactly."""
    rng = np.random.default_rng(2)
    sellers = rng.choice(600_000, 3000, replace=False)
    table = np.unique(((sellers * 4 + 1) << 16) + 1)
    edges = np.array([(1 << 32) - 1, 1 << 32, (1 << 32) + (1 << 31),
                      (1 << 38) + 5], np.int64)
    table = np.unique(np.concatenate([table, edges]))
    queries = np.concatenate([table[::3], table[::7] + 1, table[::5] - 1,
                              edges ^ 1])
    pos, found = assert_probe_parity(table, queries)
    assert found[: len(table[::3])].all()
    assert int(table.max()) > 2**37


def test_probe_negative_keys():
    table = np.array([INT64.min, -(1 << 40), -5, -1, 0, 3, 1 << 40],
                     np.int64)
    queries = np.array([INT64.min, INT64.min + 1, -(1 << 40) - 1, -5, -4,
                        -1, 0, 2, 3, INT64.max], np.int64)
    _, found = assert_probe_parity(table, queries)
    np.testing.assert_array_equal(
        found, [True, False, False, True, False, True, True, False, True,
                False])


def test_split_keys_preserves_int64_order():
    """Signed lexicographic order of the (hi, lo) words == int64 order."""
    rng = np.random.default_rng(8)
    keys = np.concatenate([
        rng.integers(INT64.min, INT64.max, 5000, dtype=np.int64),
        np.array([INT64.min, INT64.max, -1, 0, 1, (1 << 31) - 1, 1 << 31,
                  (1 << 32) - 1, 1 << 32, -(1 << 31), -(1 << 32)],
                 np.int64)])
    hi, lo = split_keys(keys)
    assert hi.dtype == lo.dtype == np.int32
    np.testing.assert_array_equal(np.lexsort((lo, hi)),
                                  np.argsort(keys, kind="stable"))
    back = (hi.astype(np.int64) << 32) \
        | (lo.view(np.uint32) ^ np.uint32(1 << 31)).astype(np.int64)
    np.testing.assert_array_equal(back, keys)


def test_probe_exact_tile_multiple():
    """Table/query sizes exactly at the kernel tile sizes (no padding)."""
    table = np.arange(TABLE_TILE, dtype=np.int64) * 3
    queries = np.arange(QUERY_BLOCK, dtype=np.int64) * 3 + 1   # all absent
    _, found = assert_probe_parity(table, queries)
    assert not found.any()


def test_bucket_ladder():
    tile = 1024
    assert [bucket(n, tile) for n in (0, 1, tile, tile + 1, 2 * tile,
                                      3 * tile, 4 * tile + 1)] \
        == [tile, tile, tile, 2 * tile, 2 * tile, 4 * tile, 8 * tile]


def test_sizes_in_one_bucket_share_one_compiled_program():
    """Padding up the bucket ladder means a new size inside a bucket is a
    cache hit, not a compile."""
    rng = np.random.default_rng(6)
    probe(np.arange(TABLE_TILE + 3), rng.integers(0, 99, 5),
          impl="interpret")
    n_probe = sorted_probe._cache_size()
    probe(np.arange(2 * TABLE_TILE - 1), rng.integers(0, 99, QUERY_BLOCK),
          impl="interpret")
    assert sorted_probe._cache_size() == n_probe
    aggregate(rng.integers(0, 9, 10).astype(np.int32), np.ones((10, 1)), 9,
              impl="interpret")
    n_agg = window_agg._cache_size()
    aggregate(rng.integers(0, 300, EVENT_TILE).astype(np.int32),
              np.ones((EVENT_TILE, 1)), SEG_BLOCK - 1, impl="interpret")
    assert window_agg._cache_size() == n_agg


# -------------------------------------------------------------- window_agg
def assert_agg_parity(seg, vals, n_segments):
    s1, c1 = aggregate(seg, vals, n_segments, impl="interpret")
    s2, c2 = aggregate(seg, vals, n_segments, impl="ref")
    np.testing.assert_allclose(s1, s2, atol=1e-3)
    np.testing.assert_array_equal(c1, c2)
    return s1, c1


def test_agg_empty_events():
    sums, counts = assert_agg_parity(np.empty(0, np.int32),
                                     np.empty((0, 3), np.float32), 16)
    assert sums.shape == (16, 3) and (sums == 0).all()
    assert (counts == 0).all()


def test_agg_zero_segments():
    sums, counts = assert_agg_parity(np.empty(0, np.int32),
                                     np.empty((0, 2), np.float32), 0)
    assert sums.shape == (0, 2) and counts.shape == (0,)


def test_agg_single_segment_all_duplicates():
    seg = np.zeros(1500, np.int32)
    vals = np.ones((1500, 1), np.float32)
    sums, counts = assert_agg_parity(seg, vals, 1)
    assert sums[0, 0] == 1500.0 and counts[0] == 1500.0


def test_agg_segment_count_off_tile():
    """n_segments just past a SEG_BLOCK boundary; events off EVENT_TILE."""
    rng = np.random.default_rng(5)
    seg = rng.integers(0, SEG_BLOCK + 1, EVENT_TILE + 1).astype(np.int32)
    vals = rng.normal(size=(EVENT_TILE + 1, 2)).astype(np.float32)
    assert_agg_parity(seg, vals, SEG_BLOCK + 1)


def test_agg_integer_weights_sum_exactly():
    """The store's weight sums: integer counts far past bfloat16's 8-bit
    mantissa must come back exact."""
    rng = np.random.default_rng(12)
    seg = np.sort(rng.integers(0, 700, 5000)).astype(np.int32)
    w = rng.integers(1, 4000, (5000, 1)).astype(np.float32)
    sums, _ = assert_agg_parity(seg, w, 700)
    np.testing.assert_array_equal(
        sums[:, 0], np.bincount(seg, weights=w[:, 0], minlength=700))


def dense_ranks(lengths) -> np.ndarray:
    """Sorted ranks 0, 0, 1, ...: segment i repeated lengths[i] times, the
    ids the store's consolidation sends."""
    return np.repeat(np.arange(len(lengths)), lengths).astype(np.int32)


def _agg_cases():
    rng = np.random.default_rng(21)
    straddle = rng.integers(1, 700, 70)            # ~24K events
    return {
        # one segment per event, shifted so a tile starts mid-block and
        # spans EVENT_TILE // SEG_BLOCK + 1 blocks
        "one_per_event": (dense_ranks([100] + [1] * (2 * EVENT_TILE)), 1),
        "one_segment_many_tiles": (dense_ranks([3 * EVENT_TILE + 5]), 1),
        "straddling_tiles_and_blocks": (dense_ranks(straddle), 1),
        # bucket(n) = 4 tiles: the last tile is all padding
        "just_over_a_bucket_edge": (dense_ranks([1] * (2 * EVENT_TILE + 1)),
                                    1),
        "several_value_rows": (dense_ranks(rng.integers(1, 40, 900)), 3),
    }


@pytest.mark.parametrize("case", list(_agg_cases()))
def test_agg_dense_sorted_ranks_match_ref(case):
    """The store's input, dense sorted ranks, goes to the banded kernel
    as it is and sums exactly."""
    seg, v = _agg_cases()[case]
    n_segments = int(seg[-1]) + 1
    vals = np.random.default_rng(3).integers(0, 9, (len(seg), v)) \
        .astype(np.float32)
    before = spans.counts["window_agg.remapped"]
    s1, c1 = aggregate(seg, vals, n_segments, impl="interpret")
    s2, c2 = aggregate(seg, vals, n_segments, impl="ref")
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(c1, c2)
    assert spans.counts["window_agg.remapped"] == before


def test_agg_integer_sums_exact_near_2_24():
    """Segment sums up to 2^24 - 1 stay exact in float32: no partial sum
    is ever larger than its segment's total."""
    w = np.concatenate([np.full(8191, 2048), [2047],        # 2^24 - 1
                        np.full(4096, 4095), [4095],        # 2^24 - 4095
                        np.arange(1, 9)]).astype(np.float32)
    seg = dense_ranks([8192, 4097] + [1] * 8)
    sums, _ = aggregate(seg, w[:, None], 10, impl="interpret")
    want = np.bincount(seg, weights=w.astype(np.int64), minlength=10)
    assert want[0] == 2**24 - 1
    np.testing.assert_array_equal(sums[:, 0].astype(np.int64), want)


@pytest.mark.parametrize("lengths", [[1] * (3 * EVENT_TILE),
                                     [100] + [1] * (2 * EVENT_TILE),
                                     [5000, 1, 1, 3 * EVENT_TILE, 7, 600]])
def test_agg_schedule_compares_each_row_with_only_its_blocks(lengths):
    """The merge path has tiles + blocks - 1 steps, visits each segment
    block in one run, and compares every event row with exactly the
    blocks its ids fall in: one row compare per (row, block) pair."""
    import jax.numpy as jnp
    seg = dense_ranks(lengths)
    nb = bucket(len(seg), EVENT_TILE)
    n_blocks = bucket(int(seg[-1]) + 1, SEG_BLOCK) // SEG_BLOCK
    padded = np.full(nb, -1, np.int32)
    padded[:len(seg)] = seg
    sched = np.asarray(agg_kernel._schedule(jnp.asarray(padded), n_blocks))
    tile, r0, r1 = (np.asarray(a) for a in agg_kernel._unpack(sched))
    block = np.arange(len(sched)) - tile
    assert len(sched) == nb // EVENT_TILE + n_blocks - 1
    assert (np.diff(tile) >= 0).all() and (np.diff(block) >= 0).all()
    assert set(block) == set(range(n_blocks))
    compared = {(t * agg_kernel.ROWS + r, b)
                for t, lo, hi, b in zip(tile, r0, r1, block)
                for r in range(lo, hi)}
    rows = np.arange(len(seg)) // LANES
    needed = set(zip(rows.tolist(), (seg // SEG_BLOCK).tolist()))
    assert compared == needed
    assert (r1 - r0).sum() == len(needed)


def test_agg_unsorted_ids_are_sorted_first_and_counted():
    rng = np.random.default_rng(4)
    seg = rng.integers(0, 2000, 9000).astype(np.int32)
    vals = rng.integers(0, 9, (9000, 2)).astype(np.float32)
    before = spans.counts["window_agg.remapped"]
    s1, c1 = aggregate(seg, vals, 2000, impl="interpret")
    assert spans.counts["window_agg.remapped"] == before + 1
    s2, c2 = aggregate(seg, vals, 2000, impl="ref")
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(c1, c2)


def test_agg_sorted_ids_with_gaps_match_ref():
    """Sorted ids that skip segments (empty blocks, a row spanning many
    blocks) need no sorting and still sum exactly."""
    rng = np.random.default_rng(9)
    seg = np.sort(np.concatenate([rng.integers(0, 60_000, 6000),
                                  np.arange(0, 60_000, 997)])).astype(np.int32)
    vals = rng.integers(0, 9, (len(seg), 1)).astype(np.float32)
    before = spans.counts["window_agg.remapped"]
    sums, _ = assert_agg_parity(seg, vals, 60_000)
    assert spans.counts["window_agg.remapped"] == before
    np.testing.assert_array_equal(
        sums[:, 0], np.bincount(seg, weights=vals[:, 0], minlength=60_000))


def test_store_sends_only_sorted_ranks(monkeypatch):
    """Consolidation, flush and snapshot on the kernel path call the
    kernel with sorted ranks: nothing is sorted again on the host."""
    calls = {"consolidate": 0}
    consolidate = LSMStore._consolidate

    def counted(self):
        calls["consolidate"] += 1
        consolidate(self)

    monkeypatch.setattr(LSMStore, "_consolidate", counted)
    rng = np.random.default_rng(13)
    store = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    before = spans.counts.copy()
    for _ in range(40):                      # 8 runs consolidate, then flush
        keys = rng.integers(0, 3_000, 10).astype(np.int64)
        store.put_batch(keys, rng.integers(0, 99, (10, 2)).astype(np.int32))
    snap = store.snapshot()
    got = spans.counts - before
    assert calls["consolidate"] > 0 and store.metrics.flushes > 0
    assert len(snap["keys"]) > 0
    assert got["window_agg.calls"] > 0
    assert got["window_agg.remapped"] == 0


# ------------------------------------------ LSM store dispatch: kernel path
def assert_store_impls_agree(lo: int, hi: int) -> None:
    """Same writes and reads, keys drawn from [lo, hi), into a numpy store
    and a kernel-backed store: every answer and metric must agree."""
    rng = np.random.default_rng(11)
    a = LSMStore(0.5, value_words=2, kernel_impl="numpy")
    b = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    for step in range(6):
        n = int(rng.integers(1, 800))
        keys = rng.integers(lo, hi, n).astype(np.int64)
        vals = rng.integers(0, 1 << 30, (n, 2)).astype(np.int32)
        a.put_batch(keys, vals)
        b.put_batch(keys, vals)
        q = rng.integers(lo, hi + 500, 300).astype(np.int64)
        ga, fa = a.get_batch(q)
        gb, fb = b.get_batch(q)
        np.testing.assert_array_equal(fa, fb, err_msg=str(step))
        np.testing.assert_array_equal(ga, gb, err_msg=str(step))
        assert a.metrics.snapshot() == b.metrics.snapshot(), step
    ka, va = a.items()
    kb, vb = b.items()
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_array_equal(va, vb)


def test_store_pallas_impl_matches_numpy_oracle():
    """The columnar store's get/put/flush behavior must not depend on which
    kernel backend serves its probes and weight sums."""
    assert_store_impls_agree(0, 2_000)


@pytest.mark.parametrize("lo,hi", [(-2_000, 2_000),
                                   ((1 << 38) - 1_000, (1 << 38) + 1_000)])
def test_store_kernel_impl_matches_numpy_oracle_on_int64_keys(lo, hi):
    """Negative keys, and q8-scale keys above 2^31."""
    assert_store_impls_agree(lo, hi)


def test_pallas_impl_refuses_to_run_off_the_tpu():
    """Compiled kernels never fall back to the interpreter or numpy."""
    with pytest.raises(RuntimeError, match="TPU"):
        LSMStore(0.5, kernel_impl="pallas")
    with pytest.raises(RuntimeError, match="TPU"):
        lsm.set_kernel_impl("pallas")
    assert lsm.DEFAULT_KERNEL_IMPL == "numpy"
    with pytest.raises(ValueError):
        LSMStore(0.5, kernel_impl="cuda")


# ------------------------------ LSM store dispatch: device-resident run keys
def _live_runs(store) -> list:
    return [r[0] for runs in (store._runs, store._tiers, store.levels)
            for r in runs]


def assert_only_live_runs_resident(store) -> None:
    res = store._resident
    if res is not None:
        assert len(res) == sum(k in res for k in _live_runs(store))


def _purge(store) -> LSMStore:
    store.purge(lambda k: k % 3 != 0)
    return store


def _resize(store) -> LSMStore:
    store.resize(0.25)
    return store


def _restore(store) -> LSMStore:
    return LSMStore.restore(store.snapshot(), kernel_impl=store.kernel_impl)


def _flush(store) -> LSMStore:
    store._flush()
    return store


def _install(store) -> LSMStore:
    """A large installed run, which compacts the levels above it."""
    store.install_run(np.arange(5_000, dtype=np.int64) * 5,
                      np.zeros((5_000, 2), np.int32))
    return store


@pytest.mark.parametrize("change", [_flush, _purge, _resize, _restore],
                         ids=["flush_merge", "purge", "resize", "restore"])
def test_resident_run_keys_match_the_numpy_store(change):
    """Reads through device-resident run keys answer exactly as the numpy
    store does across flushes, level merges and ``change``, and the
    resident map never holds a run the store has dropped."""
    rng = np.random.default_rng(17)
    a = LSMStore(0.5, value_words=2, kernel_impl="numpy")
    b = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    before = spans.counts.copy()
    written = np.empty(0, np.int64)
    for step in range(8):
        n = int(rng.integers(50, 600))
        keys = rng.integers(0, 60_000, n).astype(np.int64)
        vals = rng.integers(0, 1 << 30, (n, 2)).astype(np.int32)
        a.put_batch(keys, vals)
        b.put_batch(keys, vals)
        written = np.r_[written, keys]
        if step == 4:
            a, b = change(a), change(b)
        for _ in range(2):                  # the second read reuses runs
            q = np.r_[rng.choice(written, 200),
                      rng.integers(0, 61_000, 100)].astype(np.int64)
            ga, fa = a.get_batch(q)
            gb, fb = b.get_batch(q)
            np.testing.assert_array_equal(fa, fb, err_msg=str(step))
            np.testing.assert_array_equal(ga, gb, err_msg=str(step))
        assert a.metrics.snapshot() == b.metrics.snapshot(), step
        assert_only_live_runs_resident(b)
    got = spans.counts - before
    assert b.metrics.flushes > 0 and b.metrics.compactions > 0
    assert got["sorted_probe.table_reuses"] > 0
    assert got["sorted_probe.table_uploads"] \
        + got["sorted_probe.table_reuses"] == got["sorted_probe.calls"]
    np.testing.assert_array_equal(a.items()[0], b.items()[0])
    np.testing.assert_array_equal(a.items()[1], b.items()[1])


@pytest.mark.parametrize("k", [1, 2, 5])
def test_a_run_probed_k_times_is_uploaded_once(k):
    rng = np.random.default_rng(k)
    store = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    keys = np.sort(rng.choice(1 << 40, 9_000, replace=False))
    store.install_run(keys, np.zeros((len(keys), 2), np.int32))
    run = store.levels[0][0]
    before = spans.counts.copy()
    for _ in range(k):
        pos, hit = store._probe_run(run, keys[::7])
        assert hit.all() and (run[pos] == keys[::7]).all()
    got = spans.counts - before
    tp, qp = bucket(len(keys), TABLE_TILE), bucket(len(keys[::7]),
                                                   QUERY_BLOCK)
    assert got["sorted_probe.table_uploads"] == 1
    assert got["sorted_probe.table_reuses"] == k - 1
    assert got["sorted_probe.h2d_bytes"] == 8 * (tp + k * qp)


def _probe_every_run(store) -> None:
    for run in _live_runs(store):
        store._probe_run(run, run[:3])


@pytest.mark.parametrize("drop", [_install, _purge, _flush],
                         ids=["compaction", "purge", "flush"])
def test_resident_map_holds_only_the_live_runs(drop):
    rng = np.random.default_rng(19)
    store = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    for _ in range(12):
        keys = rng.integers(0, 2_000, 40).astype(np.int64)
        store.put_batch(keys, np.ones((40, 2), np.int32))
    assert store.levels and store._runs
    _probe_every_run(store)
    n_before = len(store._resident)
    assert n_before == len(_live_runs(store))
    drop(store)
    assert_only_live_runs_resident(store)
    assert len(store._resident) < n_before


def test_an_upload_drops_a_run_held_outside_the_store():
    """A run the store merged away while its caller still holds the
    array leaves the device at the next upload."""
    rng = np.random.default_rng(23)
    store = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    for _ in range(12):
        keys = rng.integers(0, 2_000, 40).astype(np.int64)
        store.put_batch(keys, np.ones((40, 2), np.int32))
    _probe_every_run(store)
    held = store.levels[0][0]
    _install(store)
    assert all(run is not held for run in _live_runs(store))
    assert held in store._resident          # freed only at the next upload
    _probe_every_run(store)
    assert held not in store._resident
    assert_only_live_runs_resident(store)


def test_a_dropped_store_frees_its_resident_keys():
    """Run keys held outside the store (an installed snapshot) keep
    their host array, but not the dropped store's device words."""
    import gc
    import weakref
    keys = np.arange(0, 30_000, 3, dtype=np.int64)
    store = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    store.install_run(keys, np.zeros((len(keys), 2), np.int32))
    store._probe_run(keys, keys[:10])
    device = weakref.ref(store._resident._by_id[id(keys)][1])
    del store
    gc.collect()
    assert device() is None


@pytest.mark.parametrize("tier", ["level", "memtable_run"])
def test_writing_into_an_uploaded_run_raises(tier):
    store = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    store.install_run(np.arange(100, dtype=np.int64),
                      np.zeros((100, 2), np.int32))
    store.put_batch(np.arange(5, dtype=np.int64) * 7,
                    np.ones((5, 2), np.int32))
    store.get_batch(np.arange(300, 310, dtype=np.int64))  # probes both
    run = store.levels[0][0] if tier == "level" else store._runs[0][0]
    assert run in store._resident
    with pytest.raises(ValueError, match="read-only"):
        run[0] = 1
