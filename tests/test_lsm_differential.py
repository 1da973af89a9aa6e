"""Differential store-testing harness: ``LSMStore`` vs a dict model and a
recorded trajectory.

Random op sequences (put_batch / get_batch / items / resize / snapshot /
bulk_load) drive the columnar ``LSMStore`` and check after every op:

* get_batch values + found masks and items() against a python-dict
  model with newest-write-wins semantics;
* the snapshot/restore round trip (the restored store holds exactly the
  snapshot's keys and values);
* a digest of the step's observable state against
  ``tests/data/lsm_differential_digests.json``: the op's outputs, the
  full metrics snapshot (every θ/τ input the policies read), entry_count
  (the migration payload measure), the cache geometry and the
  bit-identical CLOCK cache arrays (keys/vals/ref/hand).

The record was taken while this harness still drove the pre-columnar
store in lockstep and asserted identical observable state after every
op, so it pins that store's trajectory step for step.  It is
regenerated only under the rules for the golden traces
(docs/golden-traces.md).

This is the gate that makes ripping out store internals safe: any
store-internal change must pass this harness BEFORE a golden-trace regen
is even considered.

Sequences are generated from pinned numpy seeds so the suite needs no
optional dependencies; when ``hypothesis`` is installed an extra
property-driven case searches the same op space adversarially against
the dict model.
"""
import functools
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from repro.state.lsm import LSMStore

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

N_SEQUENCES = 220                    # acceptance floor is 200
KEYSPACE = 4_000
CACHE_ATTRS = ("cache_keys", "cache_vals", "cache_ref", "cache_hand")
RECORD_PATH = Path(__file__).parent / "data" / "lsm_differential_digests.json"


def _gen_sequence(seed: int):
    """One pinned-seed op sequence: (memory_mb, use_filter, [op...])."""
    r = np.random.default_rng(seed)
    memory_mb = float(r.choice([0.25, 0.5, 2.0]))   # tiny => flush/compact
    use_filter = seed % 5 == 0                      # annihilation coverage
    ops = []
    for _ in range(int(r.integers(6, 14))):
        kind = r.choice(["put", "put", "put", "get", "get", "items",
                         "resize", "snapshot", "bulk"])
        if kind == "put":
            n = int(r.integers(1, 1_200))
            ops.append(("put",
                        r.integers(0, KEYSPACE, n).astype(np.int64),
                        r.integers(0, 1 << 30, (n, 2)).astype(np.int32)))
        elif kind == "get":
            n = int(r.integers(1, 600))
            # duplicate-laden probes exercise the θ/τ duplicate accounting
            q = r.integers(0, KEYSPACE + 500, n).astype(np.int64)
            if n > 10 and r.random() < 0.5:
                q[n // 2:] = q[: n - n // 2]
            ops.append(("get", q))
        elif kind == "resize":
            ops.append(("resize", float(r.choice([0.25, 0.5, 2.0, 8.0]))))
        elif kind == "bulk":
            n = int(r.integers(1, 800))
            ops.append(("bulk",
                        r.integers(0, KEYSPACE, n).astype(np.int64),
                        r.integers(0, 1 << 30, (n, 2)).astype(np.int32)))
        else:
            ops.append((kind,))
    ops.append(("items",))
    return memory_mb, use_filter, ops


def _digest(store: LSMStore, outputs: tuple) -> str:
    """16-hex sha256 prefix of one step: the op's outputs, then the
    store's observable state.  Arrays fold in with dtype and shape."""
    h = hashlib.sha256()

    def fold(x) -> None:
        if isinstance(x, np.ndarray):
            a = np.ascontiguousarray(x)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
        else:
            h.update(repr(x).encode())

    for x in outputs:
        fold(x)
    fold([(k, float(v)) for k, v in sorted(store.metrics.snapshot().items())])
    fold((store.entry_count, store.memtable_cap,
          store.cache_sets, store.cache_ways))
    for attr in CACHE_ATTRS:
        fold(getattr(store, attr))
    return h.hexdigest()[:16]


def _run_sequence(seed: int) -> list[str]:
    """Drive one sequence, checking the dict model after every op;
    return the per-step digests."""
    memory_mb, use_filter, ops = _gen_sequence(seed)
    store = LSMStore(memory_mb, value_words=2)
    model: dict[int, tuple] = {}
    # bulk_load is a pre-population fast path: it installs its run BELOW
    # the live memtable and never touches the cache.  Two consequences the
    # dict model must mirror when sequences interleave bulk after puts:
    #  * a key put in the current memtable epoch shadows a later bulk_load
    #    of it forever (the flush stacks the memtable run on top), so the
    #    model keeps the put value — epoch membership is replayed from the
    #    store's flush cadence (raw write count vs memtable_cap);
    #  * a key with a cached copy keeps serving the stale cached value
    #    until CLOCK evicts it, after which the bulk value surfaces — the
    #    value is eviction-order-dependent, so such keys are "tainted" and
    #    exempt from value (not presence) checks until a put or resize
    #    makes the model authoritative again.
    epoch_puts: set[int] = set()
    tainted: set[int] = set()
    mem_count = 0
    if use_filter:
        # annihilate a third
        store.compact_filter = lambda keys: keys % 3 != 0

    digests = []
    for step, op in enumerate(ops):
        tag = f"seed={seed} step={step} op={op[0]}"
        outputs: tuple = ()
        if op[0] == "put":
            _, keys, vals = op
            store.put_batch(keys, vals)
            for k, v in zip(keys.tolist(), vals.tolist()):
                model[k] = tuple(v)
            tainted.difference_update(keys.tolist())
            off, cap = 0, store.memtable_cap
            while off < len(keys):
                take = min(cap - mem_count, len(keys) - off)
                epoch_puts.update(keys[off:off + take].tolist())
                mem_count += take
                off += take
                if mem_count >= cap:                 # flush boundary
                    epoch_puts.clear()
                    mem_count = 0
        elif op[0] == "get":
            _, q = op
            got, found = store.get_batch(q)
            outputs = (got, found)
            if not use_filter:          # the dict model has no annihilation
                for i, k in enumerate(q.tolist()):
                    assert bool(found[i]) == (k in model), tag
                    if found[i] and k not in tainted:
                        assert tuple(got[i].tolist()) == model[k], tag
        elif op[0] == "resize":
            store.resize(op[1])
            epoch_puts.clear()           # resize spills the memtable
            tainted.clear()              # ...and rebuilds an empty cache
            mem_count = 0
        elif op[0] == "bulk":
            _, keys, vals = op
            store.bulk_load(keys, vals)
            cached = set(store.cache_keys[store.cache_keys >= 0].tolist())
            for k, v in zip(keys.tolist(), vals.tolist()):
                if k in epoch_puts:      # memtable puts shadow bulk runs
                    continue
                model[k] = tuple(v)      # the levels-resident truth
                if k in cached:          # stale cached copy may serve first
                    tainted.add(k)
        elif op[0] == "items":
            keys, vals = store.items()
            outputs = (keys, vals)
            if not use_filter:
                assert set(keys.tolist()) == set(model), tag
        elif op[0] == "snapshot":
            snap = store.snapshot()
            # each occurrence counted once
            assert int(snap["weights"].sum()) >= len(snap["keys"]), tag
            rk, rv = LSMStore.restore(snap).items()
            np.testing.assert_array_equal(rk, snap["keys"], err_msg=tag)
            np.testing.assert_array_equal(rv, snap["vals"], err_msg=tag)
            outputs = (snap["keys"], snap["vals"], snap["weights"],
                       snap["memory_mb"], rk, rv)
        digests.append(_digest(store, outputs))
    return digests


@functools.cache
def _load_record() -> dict[str, list[str]]:
    return json.loads(RECORD_PATH.read_text())


@pytest.mark.parametrize("seed", range(N_SEQUENCES))
def test_store_matches_model_and_record(seed):
    got = _run_sequence(seed)
    want = _load_record()[str(seed)]
    assert len(got) == len(want), (seed, len(got), len(want))
    first = next((i for i, (g, w) in enumerate(zip(got, want)) if g != w),
                 None)
    if first is not None:
        op = _gen_sequence(seed)[2][first][0]
        pytest.fail(f"seed={seed}: step {first} ({op}) is the first to "
                    f"differ from the record: {got[first]} != {want[first]}")


def test_record_covers_every_pinned_sequence():
    """The record holds exactly the pinned seeds, one digest per op of
    each generated sequence, so it cannot shrink or drift from the
    generator unnoticed."""
    record = _load_record()
    assert sorted(record, key=int) == [str(s) for s in range(N_SEQUENCES)]
    for seed in range(N_SEQUENCES):
        digests = record[str(seed)]
        assert len(digests) == len(_gen_sequence(seed)[2]), seed
        assert all(len(d) == 16 and int(d, 16) >= 0 for d in digests), seed


def test_sequence_space_covers_all_ops():
    """The pinned seeds must actually exercise every op kind and both
    filter modes — guards against the generator silently degenerating."""
    kinds = set()
    filters = set()
    for seed in range(N_SEQUENCES):
        _, use_filter, ops = _gen_sequence(seed)
        filters.add(use_filter)
        kinds.update(op[0] for op in ops)
    assert kinds == {"put", "get", "items", "resize", "snapshot", "bulk"}
    assert filters == {True, False}


def test_get_batch_uhint_identical():
    """A hinted probe (put decomposition shifted by a constant, the join
    operator's reuse pattern) must be bit-identical to the unhinted call —
    values, found masks, metric charges, and cache arrays."""
    rng = np.random.default_rng(42)
    a = LSMStore(0.5, value_words=2)
    b = LSMStore(0.5, value_words=2)
    for step in range(8):
        n = int(rng.integers(1, 900))
        keys = rng.integers(0, 3_000, n).astype(np.int64)
        vals = rng.integers(0, 1 << 30, (n, 2)).astype(np.int32)
        da = a.put_batch(keys, vals)
        b.put_batch(keys, vals)
        q = keys + 7                    # monotone shift of the same batch
        ga, fa = a.get_batch(q, uhint=(da[0] + 7, da[1]))
        gb, fb = b.get_batch(q)
        np.testing.assert_array_equal(fa, fb, err_msg=str(step))
        np.testing.assert_array_equal(ga, gb, err_msg=str(step))
        assert a.metrics.snapshot() == b.metrics.snapshot(), step
        for attr in CACHE_ATTRS:
            np.testing.assert_array_equal(getattr(a, attr), getattr(b, attr),
                                          err_msg=str(step))


def test_weight_semantics_columnar():
    """Z-set bookkeeping: weights count
    write occurrences, survive snapshot/restore, and annihilated weight
    is tracked when the compaction filter drops keys."""
    s = LSMStore(0.25, value_words=2)
    keys = np.array([7, 7, 7, 9], np.int64)
    vals = np.arange(8, dtype=np.int32).reshape(4, 2)
    s.put_batch(keys, vals)
    snap = s.snapshot()
    w = dict(zip(snap["keys"].tolist(), snap["weights"].tolist()))
    assert w == {7: 3, 9: 1}
    r = LSMStore.restore(snap)
    assert r.total_weight() == 4
    s2 = LSMStore(0.25, value_words=2)
    s2.compact_filter = lambda k: k % 2 != 0
    s2.put_batch(np.array([2, 2, 3], np.int64),
                 np.ones((3, 2), np.int32))
    s2._flush()
    assert s2.annihilated == 2                  # both writes of key 2
    assert s2.items()[0].tolist() == [3]


if HAS_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(10_000, 10_000_000))
    def test_store_matches_model_hypothesis(seed):
        """Adversarial search over the same sequence space (extra,
        unrecorded seeds) against the dict model and the round trip."""
        _run_sequence(seed)
