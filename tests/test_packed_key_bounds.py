"""Packed-key headroom and aliasing regression tests (PR 9).

The columnar store packs (source_rank << 45) + key into one int64 so a
single searchsorted covers every memtable run, and the prewarm path
packs (set << 47) | key.  These tests pin the bit-width boundaries that
B601 (the reprolint bit-width pass) now proves statically, and the
aliasing fixes that A701 (the escape pass) surfaced:

* a query key outside [0, 2^45) must NOT false-hit another source's
  band (failing before the ``fast`` range guard in ``get_batch``);
* ``items()`` / ``snapshot()`` must hand out copies, not live views,
  even when exactly one source is live (failing before the
  single-source copy in ``_items_weighted``);
* the fast packed probe and the per-run fallback must agree key for
  key, including at the 2^45 - 1 boundary.
"""
import numpy as np
import pytest

from repro.state.lsm import LSMStore

LIM45 = np.int64(1) << np.int64(45)


def _vals(keys, words=4):
    """Deterministic per-key payloads: val[i] = key * 10 + column."""
    k = np.asarray(keys, np.int64)
    return (k[:, None] * 10 + np.arange(words)).astype(np.int32)


def _store(**kw):
    return LSMStore(64, **kw)


# ------------------------------------------------- band-collision regression
def test_out_of_band_query_key_does_not_false_hit():
    # Two delta runs: querying key 2^45 + 7 used to pack (for source
    # rank 0) onto the SAME int64 as source rank 1's stored key 7 and
    # report a hit with another key's payload.
    st = _store()
    st.put_batch(np.array([5, 7]), _vals([5, 7]))
    st.put_batch(np.array([7, 9]), _vals([7, 9]))
    ghost = int(LIM45) + 7
    vals, found = st.get_batch(np.array([ghost], np.int64))
    assert not found[0]
    assert (vals[0] == 0).all()


def test_negative_query_key_forces_fallback_without_corrupting_batch():
    # A negative key would land below every band after packing; the
    # range guard must push the whole batch to the per-run fallback and
    # leave the in-band answers untouched.  (found[-1] itself is not
    # asserted: the block cache's empty-slot sentinel is -1, a
    # pre-existing keys>=0 domain assumption outside this regression.)
    st = _store()
    st.put_batch(np.array([5, 7]), _vals([5, 7]))
    st.put_batch(np.array([7, 9]), _vals([7, 9]))
    vals, found = st.get_batch(np.array([5, 9, -1], np.int64))
    assert found[0] and found[1]
    np.testing.assert_array_equal(vals[0], _vals([5])[0])
    np.testing.assert_array_equal(vals[1], _vals([9])[0])


def test_mixed_batch_with_out_of_band_key_matches_in_band_answers():
    # One out-of-band key forces the whole batch onto the per-run
    # fallback; the in-band keys must resolve exactly as the fast path
    # resolves them on their own.
    st = _store()
    rng = np.random.default_rng(9)
    for _ in range(3):
        keys = np.sort(rng.integers(0, 1000, 32))
        st.put_batch(keys, _vals(keys))
    probe = np.array([1, 17, 500, 999], np.int64)
    fast_vals, fast_found = st.get_batch(probe)
    slow_vals, slow_found = st.get_batch(
        np.concatenate([probe, [int(LIM45) + 1]]))
    np.testing.assert_array_equal(fast_found, slow_found[:-1])
    np.testing.assert_array_equal(fast_vals, slow_vals[:-1])
    assert not slow_found[-1]


def test_stored_key_at_45_bit_boundary_still_resolves():
    # Keys >= 2^45 make _mem_concat bail; the store must still serve
    # them through the per-run fallback with the right payload.
    st = _store()
    big = int(LIM45) + 7
    keys = np.array([3, big], np.int64)
    st.put_batch(keys, _vals(keys))
    vals, found = st.get_batch(np.array([big, 3, big + 1], np.int64))
    assert found[0] and found[1] and not found[2]
    np.testing.assert_array_equal(vals[0], _vals([big])[0])
    np.testing.assert_array_equal(vals[1], _vals([3])[0])


def test_fast_path_serves_key_at_band_edge():
    # 2^45 - 1 is the largest key the packed probe may handle.
    st = _store()
    edge = int(LIM45) - 1
    keys = np.array([0, edge], np.int64)
    st.put_batch(keys, _vals(keys))
    st.put_batch(np.array([1], np.int64), _vals([1]))
    vals, found = st.get_batch(np.array([edge, 0, 1], np.int64))
    assert found.all()
    np.testing.assert_array_equal(vals[0], _vals([edge])[0])


def test_fast_and_fallback_paths_agree_key_for_key():
    # Same writes into a numpy-kernel store (fast packed probe) and a
    # kernel-backed store (always per-run fallback): reads must agree.
    a, b = _store(kernel_impl="numpy"), _store(kernel_impl="interpret")
    rng = np.random.default_rng(4)
    for _ in range(5):
        keys = rng.integers(0, 200, 48)
        vals = _vals(keys)
        a.put_batch(keys, vals)
        b.put_batch(keys, vals)
    probe = rng.integers(-5, 260, 64)
    va, fa = a.get_batch(probe)
    vb, fb = b.get_batch(probe)
    np.testing.assert_array_equal(fa, fb)
    np.testing.assert_array_equal(va, vb)


# ---------------------------------------------------- aliasing regressions
def test_items_returns_copies_even_with_single_live_source():
    # With exactly one live source _items_weighted used to return the
    # run arrays themselves; a caller mutating them corrupted the store.
    st = _store()
    keys = np.array([2, 4, 6], np.int64)
    st.put_batch(keys, _vals(keys))
    k, v = st.items()
    k2, v2 = k.copy(), v.copy()
    k[:] = -1
    v[:] = -999
    # a second derivation must see the store's own intact arrays
    k3, v3 = st.items()
    np.testing.assert_array_equal(k3, k2)
    np.testing.assert_array_equal(v3, v2)
    vals, found = st.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(vals, _vals(keys))


def test_snapshot_arrays_are_not_live_views():
    st = _store()
    keys = np.array([11, 13], np.int64)
    st.put_batch(keys, _vals(keys))
    snap = st.snapshot()
    ref = {f: snap[f].copy() for f in ("keys", "vals", "weights")}
    snap["keys"][:] = 0
    snap["vals"][:] = 0
    snap["weights"][:] = 0
    again = st.snapshot()
    for f in ("keys", "vals", "weights"):
        np.testing.assert_array_equal(again[f], ref[f])
    vals, found = st.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(vals, _vals(keys))


def test_snapshot_restore_round_trip_after_mutation():
    # The snapshot taken BEFORE extra writes must restore the old state.
    st = _store()
    keys = np.array([1, 2, 3], np.int64)
    st.put_batch(keys, _vals(keys))
    snap = st.snapshot()
    st.put_batch(keys, _vals(keys + 100))   # overwrite payloads in place?
    re = LSMStore.restore(snap)
    vals, found = re.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(vals, _vals(keys))


# ----------------------------------------------------- prewarm 47-bit pack
def test_prewarm_fused_sort_matches_fallback_at_47_bit_edge():
    # prewarm's fused (set << 47) | key sort only fires for keys below
    # 2^47; a batch straddling the limit takes the dedup fallback.  Both
    # must leave the cache answering identically for the warmed keys.
    lim47 = np.int64(1) << np.int64(47)
    lo = np.arange(64, dtype=np.int64) * 3 + 1
    vals = _vals(lo)

    fused = _store()
    fused.prewarm_cache(lo, vals)
    fallback = _store()
    big_keys = np.concatenate([lo[:-1], [int(lim47) + 5]])
    fallback.prewarm_cache(big_keys, _vals(big_keys))

    fh = fused.cache_keys.copy()
    assert (fh != -1).any()              # fused path actually warmed sets
    # warmed entries must serve hits without touching the (empty) levels
    for st, keys in ((fused, lo), (fallback, lo[:-1])):
        st.put_batch(keys, _vals(keys))  # make keys live so probes resolve
        _, found = st.get_batch(keys)
        assert found.all()


def test_prewarm_respects_45_bit_store_guard():
    # Keys above 2^45 still prewarm (the cache packs at 47 bits), and
    # subsequent reads resolve through the fallback memtable probe.
    st = _store()
    big = int(LIM45) + 123
    keys = np.array([big, big + 2], np.int64)
    st.put_batch(keys, _vals(keys))
    st.prewarm_cache(keys, _vals(keys))
    vals, found = st.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(vals, _vals(keys))


# ------------------------------------------------------- headroom asserts
def test_memtable_source_count_headroom_assert_is_lenient_in_range():
    # MEMTABLE_RUNS consolidation keeps run counts tiny; the 2^18 source
    # assert must never fire under sustained writes.
    st = _store()
    rng = np.random.default_rng(7)
    for _ in range(40):
        keys = rng.integers(0, 5000, 64)
        st.put_batch(keys, _vals(keys))
    probe = rng.integers(0, 5000, 128)
    _vals_out, _found = st.get_batch(probe)   # must not raise


def test_uint16_partition_cast_is_lossless_at_boundary():
    # engine's radix trick: argsort(part.astype(uint16)) must equal
    # argsort(part) whenever p <= 2^16 — pin the extreme p.
    p = 1 << 16
    rng = np.random.default_rng(3)
    part = rng.integers(0, p, 4096)
    a = np.argsort(part.astype(np.uint16), kind="stable")
    b = np.argsort(part, kind="stable")
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("shift", [45, 47])
def test_packed_word_round_trips_at_field_edges(shift):
    # algebraic pin of the packing identity at max field values
    s = np.int64(shift)
    hi = np.int64((1 << (63 - shift)) - 1)
    lo = np.int64((1 << shift) - 1)
    packed = (hi << s) | lo
    assert packed > 0                     # no sign-bit overflow
    assert packed >> s == hi
    assert packed & ((np.int64(1) << s) - 1) == lo
