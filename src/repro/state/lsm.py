"""Columnar delta LSM state backend — the RocksDB analogue Justin observes.

Structure mirrors §3 of the paper, rebuilt around the DBSP/gnitz Z-set
design (SNIPPETS.md §1): keys, weights and payloads live in separate
arrays (SoA), every write is an algebraic *delta*, and duplicate
resolution / compaction are batched weight-summation + annihilation
passes instead of per-put argsorts.

* **MemTable** — an append-only stack of sorted-unique delta runs over a
  consolidated base.  ``put_batch`` appends one delta run per batch
  (keys, per-key occurrence weights, newest payloads); nothing else is
  touched on the write path.  When the stack reaches ``MEMTABLE_RUNS``
  runs it is *consolidated*: one stable sort over the concatenated runs,
  a segment weight-sum per unique key (the ``window_agg`` kernel's job on
  TPU), newest payload wins, then an O(n) scatter-merge into the base.
  This amortizes the O(memtable) work the old store paid on (almost)
  every put to once per ``MEMTABLE_RUNS`` batches.
* **Block cache** — set-associative CLOCK cache, unchanged and still
  bit-identical to the sequential reference scan (its hit rate is
  Justin's θ metric).
* **Levels** — sorted-unique (keys, weights, payloads) runs with
  size-tiered compaction.  Merges are O(n) two-pointer-style scatter
  merges: duplicate keys *add weights* (delta addition), the newer
  payload wins, and compaction-filter drops are *annihilations* (tracked
  in ``annihilated``).  Probes are batched sorted-run ranks — the
  ``sorted_probe`` kernel's job on TPU.

Every kernel dispatch point has a numpy path (``kernel_impl="numpy"``,
the default and the oracle).  ``kernel_impl="pallas"`` routes probes and
segment sums through the compiled ``repro.kernels`` on a TPU and refuses
to run anywhere else; ``"interpret"`` runs the same kernels in the Pallas
interpreter, which is how CPU tests cover them.  Keys reach the device as
two int32 words each (lossless over int64); a run's key words go once,
at its first probe, and stay while the store holds the run.  Weight sums
on the device are float32 — exact below 2^24, far above any per-flush
occurrence count.

Byte accounting uses the paper's *logical* entry size (1000 B values, as
in the §3 microbenchmarks) while physical storage keeps ``value_words``
int32 words per entry, so cache-capacity ratios match the paper exactly
at 1/64th the RAM.

Decision-identity invariants (pinned by ``tests/test_engine_fastpath.py``,
``tests/test_lsm_differential.py`` against a dict model and a recorded
per-step digest of the observable state, and the golden traces):

* reads see newest-write-wins values, identical to the old maintained
  view (runs are probed newest-first);
* every metric charge is structure-independent and unchanged: flat
  memtable latency per read, flush cadence on the *raw* write count,
  flush/compaction charges on deduped run lengths, θ/τ duplicate-probe
  accounting exactly as documented on ``get_batch``;
* CLOCK cache state stays bit-for-bit equal to the sequential scan;
* ``items()``/``snapshot()`` return frozen arrays (consolidation always
  allocates; nothing mutates a published array in place).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.obs.spans import span

LOGICAL_ENTRY_BYTES = 1_000          # paper §3: 1000 B events
MEMTABLE_GRANULARITY_MB = 64         # first-level SSTable size (paper §3)
CACHE_OVERHEAD = 2.5                 # block granularity + index/filter share
                                     # (RocksDB caches blocks, not entries)
MEMTABLE_RUNS = 8                    # delta runs absorbed before a
                                     # consolidation pass

KERNEL_IMPLS = ("numpy", "pallas", "interpret")   # oracle first
DEFAULT_KERNEL_IMPL = "numpy"

# CLOCK-scan lookup tables for the 8-way cache: ref bits of one set pack
# into a byte, so "first zero way at/after the hand" and "unpack ref byte
# to the [W] int8 row" become O(1) table gathers per set.
_CLOCK_POW2 = (1 << np.arange(8)).astype(np.uint8)
_CLOCK_UNPACK = ((np.arange(256)[:, None] >> np.arange(8)) & 1).astype(np.int8)
_CLOCK_FIRST_ZERO = np.where(np.arange(256) == 255, 8,
                             np.argmin(_CLOCK_UNPACK, axis=1)).astype(np.int64)


def _check_kernel_impl(name: str) -> None:
    if name not in KERNEL_IMPLS:
        raise ValueError(f"unknown kernel impl {name!r}")
    if name == "pallas":
        from repro.kernels.device import require_tpu
        require_tpu()


def set_kernel_impl(name: str) -> None:
    """Default probe/segment-sum backend for newly built stores."""
    global DEFAULT_KERNEL_IMPL
    _check_kernel_impl(name)
    DEFAULT_KERNEL_IMPL = name


def stable_argsort_keys(a: np.ndarray) -> np.ndarray:
    """Stable argsort for int64 key arrays.  numpy's stable kind only
    radix-sorts dtypes up to 16 bits, so non-negative keys below 2^32 are
    sorted in two 16-bit radix passes (LSB first) — several times faster
    than the int64 mergesort on large arrays, with an identical
    permutation (LSB->MSB radix is stable at every pass).  Anything out
    of range falls back to the mergesort."""
    n = len(a)
    if n < 4096:
        return np.argsort(a, kind="stable")
    if int(a.min()) < 0 or int(a.max()) >= (1 << 32):
        return np.argsort(a, kind="stable")
    lo = (a & 0xFFFF).astype(np.uint16)
    hi = (a >> 16).astype(np.uint16)
    o1 = np.argsort(lo, kind="stable")
    o2 = np.argsort(hi[o1], kind="stable")
    return o1[o2]


def merge_delta_runs(k1: np.ndarray, w1: np.ndarray, v1: np.ndarray,
                     k2: np.ndarray, w2: np.ndarray, v2: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Merge two sorted-unique delta runs: weights of duplicate keys ADD
    (Z-set delta addition), side-1 (newer) payloads win.  O(n) scatter
    instead of re-sorting the concatenation.  Inputs are never mutated,
    so published runs stay frozen."""
    if not len(k1):
        return k2, w2, v2
    if not len(k2):
        return k1, w1, v1
    pos = np.searchsorted(k1, k2)
    dup = (k1[np.minimum(pos, len(k1) - 1)] == k2) & (pos < len(k1))
    if dup.any():
        w1 = w1.copy()
        w1[pos[dup]] += w2[dup]       # k2 unique => conflict-free scatter
        keep = ~dup
        pos = pos[keep]
        k2, w2, v2 = k2[keep], w2[keep], v2[keep]
    out_k = np.empty(len(k1) + len(k2), k1.dtype)
    out_w = np.empty(len(out_k), w1.dtype)
    out_v = np.empty((len(out_k),) + v1.shape[1:], v1.dtype)
    # both interleave maps fall out of the one searchsorted above: the
    # surviving k2 sit strictly between k1 entries, so the k2 slot is its
    # rank plus its insert position, and the k1 slot shifts by the count
    # of k2 inserted at or before it (a bincount running sum — no further
    # log-n probes)
    i2 = np.arange(len(k2)) + pos
    cum = np.cumsum(np.bincount(pos, minlength=len(k1) + 1))
    i1 = np.arange(len(k1)) + cum[:len(k1)]
    out_k[i1], out_w[i1], out_v[i1] = k1, w1, v1
    out_k[i2], out_w[i2], out_v[i2] = k2, w2, v2
    return out_k, out_w, out_v


@dataclass
class LSMMetrics:
    reads: int = 0
    writes: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    memtable_hits: int = 0
    level_probes: int = 0            # SSTable lookups (slow tier)
    flushes: int = 0
    compactions: int = 0
    access_latency_total_ms: float = 0.0

    def snapshot(self) -> dict:
        return dict(self.__dict__)

    def counters(self) -> tuple:
        """O(1) hot-path view for the engine's per-task charge marks
        (no dict allocation): (reads, writes, cache_hits, cache_misses,
        level_probes, access_latency_total_ms)."""
        return (self.reads, self.writes, self.cache_hits, self.cache_misses,
                self.level_probes, self.access_latency_total_ms)

    def maintenance(self) -> tuple[int, int]:
        """(flushes, compactions) — the background-work counters
        ``counters()`` deliberately omits from the hot-path view; the
        observability layer reads them for per-window LSM spans."""
        return (self.flushes, self.compactions)

    def reset(self) -> None:
        for k in self.__dict__:
            setattr(self, k, 0 if not k.startswith("access") else 0.0)

    @property
    def cache_hit_rate(self) -> float:
        tot = self.cache_hits + self.cache_misses
        return self.cache_hits / tot if tot else 1.0

    @property
    def avg_access_latency_ms(self) -> float:
        tot = self.reads + self.writes
        return self.access_latency_total_ms / tot if tot else 0.0


@dataclass(frozen=True)
class LatencyModel:
    """Calibrated per-access costs (ms).  The slow tier models SSD/host-DRAM
    fetches (a cold SSTable block read on the paper's testbed is ~0.5 ms
    including read amplification); write costs amortize WAL + flush +
    compaction work, which the store *charges as it actually happens*, so
    memtable size shapes write performance the way §3 observes."""
    memtable_ms: float = 0.002
    cache_ms: float = 0.002
    level_ms: float = 0.5            # per level probed on a miss
    bloom_ms: float = 0.001          # bloom check for an absent key
    bloom_fp: float = 0.01           # bloom false-positive rate
    meta_ratio: float = 8.0          # data entries per filter/index-block
                                     # cache-entry-equivalent: metadata
                                     # (blooms + index blocks) competes for
                                     # block cache at ~1/8 the footprint
    meta_read_frac: float = 0.5      # cost of a filter-block disk read,
                                     # as a fraction of a data-block read
    write_ms: float = 0.07           # WAL append share
    flush_ms: float = 0.14           # per entry flushed to L0
    compact_ms: float = 0.05         # per entry rewritten in a merge
    flush_fixed_ms: float = 150.0    # write-stall per flush (small memtables
                                     # flush more often -> §3 (1;128) dip)


class LSMStore:
    """Columnar delta LSM over int64 keys -> fixed-width int32 payloads,
    with per-key int64 weights (write-occurrence counts)."""

    def __init__(self, memory_mb: float, *, value_words: int = 4,
                 fanout: int = 8, latency: LatencyModel | None = None,
                 entry_bytes: int = LOGICAL_ENTRY_BYTES, seed: int = 0,
                 kernel_impl: str | None = None):
        self.value_words = value_words
        self.entry_bytes = entry_bytes            # logical entry size
        self._wscale = entry_bytes / LOGICAL_ENTRY_BYTES  # IO-cost scaling
        self.latency = latency or LatencyModel()
        self.metrics = LSMMetrics()
        self.compact_filter = None                # optional keys->keep mask
        self.kernel_impl = kernel_impl or DEFAULT_KERNEL_IMPL
        _check_kernel_impl(self.kernel_impl)
        self.annihilated = 0          # weight dropped by compaction filters
        self._resident = None         # device words of probed runs
        self._configure_memory(memory_mb)
        # sorted-unique (keys, weights, vals) runs, newest first
        self.levels: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.fanout = fanout
        self._empty()

    # -- memory layout (paper §3: memtable <= 64 MB, >= half to cache, pow2) --
    def _configure_memory(self, memory_mb: float) -> None:
        self.memory_mb = float(memory_mb)
        mem_budget = memory_mb * 1024 * 1024
        memtable_b = MEMTABLE_GRANULARITY_MB * 1024 * 1024
        while memtable_b >= mem_budget / 2:    # cache gets MORE than half
            memtable_b //= 2                   # (paper §3: 128 -> 32+96)
        cache_b = mem_budget - memtable_b
        self.memtable_cap = max(64, int(memtable_b // self.entry_bytes))
        n_cache = max(64, int(cache_b // (self.entry_bytes
                                          * CACHE_OVERHEAD)))
        self.cache_ways = 8
        self.cache_sets = max(8, n_cache // self.cache_ways)

    def _empty(self) -> None:
        self.mem_n = 0                # RAW write count (flush cadence key)
        self._runs: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        # collapsed delta tiers, newest first, geometrically merged so the
        # total consolidation work stays O(n log n) over a memtable epoch
        # (a single base would re-merge its whole length every
        # MEMTABLE_RUNS batches — quadratic)
        self._tiers: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.cache_keys = np.full((self.cache_sets, self.cache_ways), -1,
                                  np.int64)
        self.cache_vals = np.zeros(
            (self.cache_sets, self.cache_ways, self.value_words), np.int32)
        self.cache_ref = np.zeros((self.cache_sets, self.cache_ways), np.int8)
        self.cache_hand = np.zeros(self.cache_sets, np.int32)
        self._cache_virgin = True        # enables the closed-form first fill
        self._mbt = None                 # batched memtable probe cache

    # ------------------------------------------------------------------ util
    @property
    def entry_count(self) -> int:
        return self.mem_n + sum(len(k) for k, _, _ in self.levels)

    @property
    def state_mb(self) -> float:
        """Logical state footprint — what migration planning prices."""
        return self.entry_count * self.entry_bytes / 2**20

    def total_weight(self) -> int:
        """Live delta weight across memtable + levels (diagnostic)."""
        w = sum(int(r[1].sum()) for r in self._runs)
        w += sum(int(t[1].sum()) for t in self._tiers)
        return w + sum(int(lw.sum()) for _, lw, _ in self.levels)

    def resize(self, memory_mb: float) -> None:
        """Vertical rescale: rebuild memtable/cache under the new budget,
        spilling the old memtable into level 0 (a Flink-style reconfig).
        Spills the consolidated deduped runs (levels must hold sorted-unique
        runs for the batched probes)."""
        if self.mem_n:
            self._push_run(*self._memtable_merged())
        self._configure_memory(memory_mb)
        self._empty()

    def items(self) -> tuple[np.ndarray, np.ndarray]:
        """All live (key, value) pairs — used for state re-partitioning.
        Memtable wins over levels; newest write wins within the memtable —
        exactly what ``get_batch`` returns."""
        k, _, v = self._items_weighted()
        return k, v

    def _items_weighted(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        # one N-way collapse over every live run, ordered oldest -> newest
        # (levels bottom-up, then tiers bottom-up, then delta runs in
        # arrival order) so the stable sort keeps the newest payload last
        # in each key group — cheaper than a pairwise merge cascade.
        sources = [s for s in (self.levels[::-1] + self._tiers[::-1]
                               + self._runs) if len(s[0])]
        acc = self._collapse(sources)
        if len(sources) == 1:
            # single live source: _collapse passes the run's arrays
            # through untouched, so hand the caller copies — items() and
            # snapshot() are public, and a caller mutating (or keeping)
            # these across a put_batch must not corrupt the live run
            acc = tuple(a.copy() for a in acc)
        return acc

    # ---------------------------------------------------------- kernel hooks
    def _probe_run(self, run_keys: np.ndarray, queries: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Batched sorted-run rank: (clipped position, hit mask).  Positions
        are only meaningful where ``hit`` — there they index the match."""
        with span("lsm.probe"):
            if self.kernel_impl != "numpy":
                from repro.kernels.sorted_probe.ops import probe
                pos, hit = probe(self._device_keys(run_keys), queries,
                                 impl=self.kernel_impl)
                return np.minimum(pos.astype(np.int64),
                                  max(len(run_keys) - 1, 0)), hit
            pos = np.searchsorted(run_keys, queries)
            pos_c = np.minimum(pos, len(run_keys) - 1)
            hit = (run_keys[pos_c] == queries) & (pos < len(run_keys))
            return pos_c, hit

    def _device_keys(self, run_keys: np.ndarray):
        """``run_keys``' padded words on the device: uploaded at the run's
        first probe (never inside a flush, merge or install) and kept
        while the store holds the run, so every later probe sends only
        its queries.  Runs are never changed once built; an uploaded run's
        keys are made read-only all the same."""
        if self._resident is None:
            from repro.kernels.sorted_probe.ops import ResidentTables
            self._resident = ResidentTables()
        return self._resident.get(run_keys, live=(
            r[0] for runs in (self._runs, self._tiers, self.levels)
            for r in runs))

    def _segment_sum(self, sorted_w: np.ndarray, starts: np.ndarray,
                     first_mask: np.ndarray) -> np.ndarray:
        """Per-unique-key weight sum over key-sorted deltas — the
        consolidation reduction (``window_agg`` kernel on TPU)."""
        with span("lsm.segment_sum"):
            if self.kernel_impl != "numpy":
                from repro.kernels.window_agg.ops import aggregate
                gids = (np.cumsum(first_mask) - 1).astype(np.int32)
                sums, _ = aggregate(gids, sorted_w.astype(np.float32)[:, None],
                                    len(starts), impl=self.kernel_impl)
                return sums[:, 0].astype(np.int64)
            return np.add.reduceat(sorted_w, starts)

    # ------------------------------------------------------------- write path
    @staticmethod
    def _dedup_newest(keys: np.ndarray, vals: np.ndarray
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Sorted unique keys with the newest (last-written) value each."""
        rk = keys[::-1]
        uq, first = np.unique(rk, return_index=True)
        return uq, vals[::-1][first]

    @staticmethod
    def _delta_of(keys: np.ndarray, vals: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One write batch as a delta run: sorted unique keys, per-key
        occurrence weight, newest payload."""
        rk = keys[::-1]
        uq, first, cnt = np.unique(rk, return_index=True, return_counts=True)
        return uq, cnt.astype(np.int64), vals[::-1][first]

    def put_batch(self, keys: np.ndarray, vals: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Apply one write batch; returns its delta decomposition
        (sorted unique keys, occurrence weights, newest payloads) so a
        caller probing a monotone transform of the same key batch can
        reuse the sort via ``get_batch``'s ``uhint`` (DBSP idiom: sort a
        batch once, feed every operator from the same arrangement)."""
        with span("lsm.put_batch"):
            n = len(keys)
            self.metrics.writes += n
            self.metrics.access_latency_total_ms += \
                n * self.latency.write_ms * self._wscale
            uq, w, uv = self._delta_of(keys, vals)   # shared by runs + cache
            if n <= self.memtable_cap - self.mem_n:  # fast path: fits in room
                self.mem_n += n
                self._append_delta(uq, w, uv)
                if self.mem_n >= self.memtable_cap:
                    self._flush()
            else:                                    # crosses flush boundaries
                off = 0
                while off < n:
                    room = self.memtable_cap - self.mem_n
                    take = min(room, n - off)
                    sl = slice(off, off + take)
                    self.mem_n += take
                    off += take
                    self._append_delta(*self._delta_of(keys[sl], vals[sl]))
                    if self.mem_n >= self.memtable_cap:
                        self._flush()
            # write-through invalidate/update of cached copies
            self._cache_apply(uq, uv)
            return uq, w, uv

    def _append_delta(self, uq: np.ndarray, w: np.ndarray, uv: np.ndarray
                      ) -> None:
        self._runs.append((uq, w, uv))
        if len(self._runs) >= MEMTABLE_RUNS:
            self._consolidate()

    def _collapse(self, sources: list[tuple[np.ndarray, np.ndarray,
                                            np.ndarray]]
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """N-way collapse of delta runs ordered OLDEST -> NEWEST: one stable
        sort over the concatenation, segment weight-sum per unique key
        (``window_agg`` on TPU), newest payload wins (last in each key
        group under the stable sort)."""
        sources = [s for s in sources if len(s[0])]
        if not sources:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    np.empty((0, self.value_words), np.int32))
        if len(sources) == 1:
            return sources[0]
        keys = np.concatenate([r[0] for r in sources])
        wts = np.concatenate([r[1] for r in sources])
        vals = np.concatenate([r[2] for r in sources])
        order = stable_argsort_keys(keys)            # ties stay oldest->newest
        sk = keys[order]
        first = np.empty(len(sk), bool)
        first[0] = True
        np.not_equal(sk[1:], sk[:-1], out=first[1:])
        starts = np.flatnonzero(first)
        out_w = self._segment_sum(wts[order], starts, first)
        last = np.empty(len(starts), np.int64)
        last[:-1] = starts[1:] - 1
        last[-1] = len(sk) - 1
        return sk[starts], out_w, vals[order[last]]

    def _consolidate(self) -> None:
        """Collapse the delta-run stack into one tier, then geometrically
        merge tiers (a tier absorbs its neighbor once it has grown to at
        least half its size) — amortized O(n log n) per memtable epoch."""
        if not self._runs:
            return
        with span("lsm.consolidate"):
            self._tiers.insert(0, self._collapse(self._runs))
            self._runs = []
            while (len(self._tiers) > 1
                   and 2 * len(self._tiers[0][0]) >= len(self._tiers[1][0])):
                newer = self._tiers.pop(0)
                self._tiers[0] = merge_delta_runs(*newer, *self._tiers[0])

    def _memtable_merged(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Full memtable content: sorted unique keys, summed weights, newest
        payloads.  Commits the pending consolidation."""
        if self._runs or len(self._tiers) > 1:
            merged = self._collapse(self._tiers[::-1] + self._runs)
            self._runs = []
            self._tiers = [merged]
        return self._tiers[0] if self._tiers else (
            np.empty(0, np.int64), np.empty(0, np.int64),
            np.empty((0, self.value_words), np.int32))

    def bulk_load(self, keys: np.ndarray, vals: np.ndarray,
                  weights: np.ndarray | None = None) -> None:
        """Pre-population fast path: dedupe (newest wins, like ``_flush``)
        and install everything as one sorted run, bypassing the memtable and
        its flush/compaction churn.  No latency is charged and no metrics are
        touched — callers reset metrics after warming anyway.  The live
        entry set is identical to an equivalent ``put_batch`` sequence.
        ``weights`` (for already-deduped input) preserves delta weights
        across snapshot/restore; without it each occurrence weighs 1."""
        if len(keys) == 0:
            return
        if weights is not None:
            self.levels.insert(0, (keys, np.asarray(weights, np.int64), vals))
            return
        self.levels.insert(0, self._delta_of(keys, vals))

    def install_run(self, keys: np.ndarray, vals: np.ndarray,
                    weights: np.ndarray | None = None) -> None:
        """Engine state-install entry point: ``keys`` already key-sorted
        (the re-partitioning path pre-sorts), installed as one run with
        size-tiered compaction applied."""
        with span("lsm.install_run"):
            if weights is None:
                weights = np.ones(len(keys), np.int64)
            self._push_run(keys, weights, vals)

    def purge(self, keep) -> None:
        """Delete every entry whose key ``keep`` (a keys -> bool mask
        function) rejects, from every memtable run, tier and level and
        from the cache, at once: a window's state cleared when the
        window ends.  A ``compact_filter`` drops only from the run being
        flushed or merged, so an older version in another level would
        be read again; this leaves none.  The dropped weight counts as
        annihilated; the flush cadence (``mem_n``) is unchanged."""
        def cut(runs):
            out = []
            for k, w, v in runs:
                m = keep(k)
                if not m.all():
                    self.annihilated += int(w[~m].sum())
                    k, w, v = k[m], w[m], v[m]
                if len(k):
                    out.append((k, w, v))
            return out
        self._runs = cut(self._runs)
        self._tiers = cut(self._tiers)
        self.levels = cut(self.levels)
        gone = (self.cache_keys >= 0) & ~keep(self.cache_keys)
        self.cache_keys[gone] = -1
        self.cache_ref[gone] = 0

    def _flush(self) -> None:
        if self.mem_n == 0:
            return
        with span("lsm.flush"):
            uniq, wts, fvals = self._memtable_merged()
            if self.compact_filter is not None and len(uniq):
                keep = self.compact_filter(uniq)
                if not keep.all():
                    self.annihilated += int(wts[~keep].sum())
                    uniq, wts, fvals = uniq[keep], wts[keep], fvals[keep]
            self._push_run(uniq, wts, fvals)
            self.mem_n = 0
            self._runs = []
            self._tiers = []
            self.metrics.flushes += 1
            self.metrics.access_latency_total_ms += \
                (len(uniq) * self.latency.flush_ms
                 + self.latency.flush_fixed_ms) * self._wscale

    def _push_run(self, keys: np.ndarray, weights: np.ndarray,
                  vals: np.ndarray) -> None:
        self.levels.insert(0, (keys, weights, vals))
        # size-tiered compaction: merge while a level outgrows fanout^i
        base = max(self.memtable_cap, 1)
        i = 0
        while i < len(self.levels) - 1:
            if len(self.levels[i][0]) >= base * (self.fanout ** i):
                self._merge_levels(i)
                self.metrics.compactions += 1
            else:
                i += 1

    def _merge_levels(self, i: int) -> None:
        with span("lsm.compact"):
            k1, w1, v1 = self.levels[i]          # newer
            k2, w2, v2 = self.levels[i + 1]      # older
            n_in = len(k1) + len(k2)
            uniq, wts, vals = merge_delta_runs(k1, w1, v1, k2, w2, v2)
            if self.compact_filter is not None and len(uniq):
                keep = self.compact_filter(uniq)
                if not keep.all():
                    self.annihilated += int(wts[~keep].sum())
                    uniq, wts, vals = uniq[keep], wts[keep], vals[keep]
            self.levels[i + 1] = (uniq, wts, vals)
            del self.levels[i]
            self.metrics.access_latency_total_ms += \
                n_in * self.latency.compact_ms * self._wscale

    # -------------------------------------------------------------- read path
    def get_batch(self, keys: np.ndarray,
                  uhint: tuple[np.ndarray, np.ndarray] | None = None
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Returns (values [n, V], found mask [n]) and updates θ/τ metrics.

        ``uhint`` is an optional precomputed ``(unique_keys, counts)`` for
        ``keys`` — callers that just wrote a batch whose sort order matches
        (e.g. the same events keyed for the opposite join side) pass the
        ``put_batch`` decomposition through a monotone shift and skip the
        sort here; the inverse map is recovered with one searchsorted.
        The hint MUST equal ``np.unique(keys, return_counts=True)`` —
        results and metric charges are then bit-identical to the unhinted
        call.

        Duplicate-probe accounting (unchanged from the fast-path engine):
        the block cache is probed once per *unique* key; duplicate
        occurrences of a resolved key are charged as hits on the
        just-admitted block, duplicates of absent keys re-walk the bloom
        filters.  Per-call metric equality on arbitrary batches vs the
        chunked seed is NOT claimed — golden-trace decision equality is."""
        with span("lsm.get_batch"):
            n = len(keys)
            self.metrics.reads += n
            lat = 0.0
            # every tier below works on unique keys: all occurrences of a key
            # resolve identically, so probe once and scatter through ``inv`` at
            # the end — occurrence-level metric charges recovered via ``cnts``
            if uhint is None:
                uq, inv, cnts = np.unique(keys, return_inverse=True,
                                          return_counts=True)
            else:
                uq, cnts = uhint
                inv = np.searchsorted(uq, keys)
            uvals = np.zeros((len(uq), self.value_words), np.int32)
            ufound = np.zeros(len(uq), bool)

            # 1. memtable: probe delta runs newest-first, then the tiers — the
            # first run containing a key holds its newest payload.  One
            # source-major searchsorted covers every run at once (see
            # _mem_concat); the per-run loop remains as the fallback for the
            # device kernel dispatch and out-of-range keys.  Both find the same
            # key set with the same newest payload, so θ/τ charges agree.
            if self.mem_n:
                with span("lsm.read.memtable"):
                    self._read_memtable(uq, cnts, uvals, ufound)
            lat += n * self.latency.memtable_ms

            # 2. block cache — probed once per *unique* key (see docstring).
            if not ufound.all():
                sub = np.flatnonzero(~ufound)
                uk = uq[sub]
                n_todo = n - int(cnts[ufound].sum())   # unfound occurrences
                with span("lsm.read.cache"):
                    sets = self._sets(uk)
                    match = self.cache_keys[sets] == uk[:, None]  # [u, ways]
                    # argmax-then-gather: one reduction pass instead of
                    # any+argmax (axis-wise ``any`` costs a full second
                    # pass; an all-False row argmaxes to way 0 where the
                    # gather reads False)
                    way = match.argmax(axis=1)
                    hit = match[np.arange(len(uk)), way]
                    hi = np.flatnonzero(hit)
                    sh, wh = sets[hi], way[hi]
                    ckvals = np.zeros((len(uk), self.value_words), np.int32)
                    ckvals[hi] = self.cache_vals[sh, wh]
                    # safe alias: ~hit is consumed (rem) before ckfound's
                    # only mutation below
                    ckfound = hit
                    self.cache_ref[sh, wh] = 1
                    self.metrics.cache_hits += len(hi)
                    self.metrics.cache_misses += len(uk) - len(hi)
                lat += len(uk) * self.latency.cache_ms

                # 3. levels (slow tier) for cache misses.  Bloom filters guard
                # each SSTable: absent keys cost a filter check (plus the
                # false-positive rate of real probes) instead of a full read.
                rem = np.where(~hit)[0]
                if len(rem):
                    with span("lsm.read.levels"):
                        got, gvals, lv_ms = self._read_levels(uk[rem])
                    ckvals[rem[got]] = gvals[got]
                    ckfound[rem[got]] = True
                    lat += lv_ms

                uvals[sub] = ckvals
                ufound[sub] = ckfound
                n_dup = n_todo - len(uk)
                if n_dup:
                    res_dups = int((cnts[sub][ckfound] - 1).sum())
                    unres_dups = n_dup - res_dups
                    # resolved duplicates hit the (possibly just-admitted)
                    # block
                    self.metrics.cache_hits += res_dups
                    self.metrics.cache_misses += unres_dups
                    lat += n_dup * self.latency.cache_ms
                    if unres_dups:
                        probes = 0.0
                        for (lk, _lw, _lv) in self.levels:
                            meta_ws = max(1.0,
                                          len(lk) / self.latency.meta_ratio)
                            meta_cover = min(1.0,
                                             self.cache_capacity / meta_ws)
                            probes += (self.latency.bloom_fp
                                       + (1.0 - meta_cover)
                                       * self.latency.meta_read_frac
                                       ) * unres_dups
                        self.metrics.level_probes += int(probes)
                        lat += (probes * self.latency.level_ms + unres_dups
                                * len(self.levels) * self.latency.bloom_ms)

            self.metrics.access_latency_total_ms += lat
            return uvals[inv], ufound[inv]

    def _read_memtable(self, uq: np.ndarray, cnts: np.ndarray,
                       uvals: np.ndarray, ufound: np.ndarray) -> None:
        """The memtable tier of ``get_batch``: fills ``uvals``/``ufound``
        for the sorted-unique keys ``uq`` (occurrence counts ``cnts``)
        that some memtable run holds."""
        T = None
        if self.kernel_impl == "numpy":
            T, offs, srcs = self._mem_concat()
        fast = False
        if T is not None and len(T) and len(uq):
            # stored keys are in [0, 2^45) (else _mem_concat bailed),
            # but QUERY keys arrive unchecked: a query outside that
            # range would land in another source's band after packing
            # and false-hit its keys, so such batches (and empty
            # query sets) take the per-run fallback below
            lim = np.int64(1) << self._MEM_SHIFT
            fast = bool(int(uq[0]) >= 0 and int(uq[-1]) < lim)
        if fast:
            R = len(srcs)
            assert R < (1 << 18)   # source ids share the 63-45 headroom
            nu = len(uq)
            qq = ((np.arange(R, dtype=np.int64)[:, None]
                   << self._MEM_SHIFT) + uq[None, :]).ravel()
            pos = np.searchsorted(T, qq)
            np.minimum(pos, len(T) - 1, out=pos)
            hit = (T[pos] == qq).reshape(R, nu)[::-1]  # newest first
            si = hit.argmax(axis=0)
            fnd = hit[si, np.arange(nu)]
            fidx = np.flatnonzero(fnd)
            if len(fidx):
                src = R - 1 - si[fidx]          # undo the flip
                ufound[fidx] = True
                self.metrics.memtable_hits += int(cnts[fidx].sum())
                posm = pos.reshape(R, nu)
                for i in np.flatnonzero(np.bincount(src, minlength=R)):
                    sel = fidx[src == i]
                    uvals[sel] = srcs[i][2][posm[i, sel] - offs[i]]
        else:
            mem_hits = 0
            pending = None               # None => every key outstanding
            for rk, _w, rv in self._mem_probe_order():
                if not len(rk):
                    continue
                if pending is None:
                    tk = uq
                else:
                    if not len(pending):
                        break
                    tk = uq[pending]
                pos, hit = self._probe_run(rk, tk)
                hidx = np.flatnonzero(hit)
                if len(hidx):
                    idx = hidx if pending is None else pending[hidx]
                    uvals[idx] = rv[pos[hidx]]
                    ufound[idx] = True
                    mem_hits += int(cnts[idx].sum())   # per-occurrence
                pending = np.flatnonzero(~hit) if pending is None \
                    else pending[~hit]
            self.metrics.memtable_hits += mem_hits

    def _read_levels(self, probe_keys: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, float]:
        """The level tier of ``get_batch`` for the sorted-unique keys
        the cache missed: (found mask, payloads, latency charged in
        ms), with the found entries admitted into the cache."""
        got = np.zeros(len(probe_keys), bool)
        gvals = np.zeros((len(probe_keys), self.value_words), np.int32)
        probes = 0.0
        blooms = 0
        for (lk, _lw, lv) in self.levels:
            lidx = np.flatnonzero(~got)
            n_live = len(lidx)
            if not n_live:
                break
            if len(lk):
                pos, h = self._probe_run(lk, probe_keys[lidx])
            else:
                h = np.zeros(n_live, bool)
                pos = h
            n_hit = int(h.sum())
            # present keys pass the bloom filter and read the block;
            # absent keys mostly stop at the filter — but the filter/
            # index blocks themselves need block-cache residency:
            # with a small cache a share of filter checks also hits
            # the slow tier (RocksDB filter-block eviction)
            meta_ws = max(1.0, len(lk) / self.latency.meta_ratio)
            meta_cover = min(1.0, self.cache_capacity / meta_ws)
            probes += n_hit + self.latency.bloom_fp * (n_live - n_hit)
            probes += (1.0 - meta_cover) \
                * self.latency.meta_read_frac * n_live
            blooms += n_live
            if n_hit:
                hh = np.flatnonzero(h)
                tgt = lidx[hh]
                gvals[tgt] = lv[pos[hh]]
                got[tgt] = True
        self.metrics.level_probes += int(probes)
        # admit fetched entries into the cache (probe_keys is
        # sorted-unique, so the deduping _cache_update is skipped)
        if got.any():
            self._cache_apply(probe_keys[got], gvals[got], fresh=True)
        return got, gvals, (probes * self.latency.level_ms
                            + blooms * self.latency.bloom_ms)

    def _mem_probe_order(self):
        """Memtable runs in read-priority order: newest delta run first,
        then the collapsed tiers (themselves newest-first)."""
        for i in range(len(self._runs) - 1, -1, -1):
            yield self._runs[i]
        yield from self._tiers

    _MEM_SHIFT = np.int64(45)            # source-major probe prefix width

    def _mem_concat(self):
        """Source-major concat of every memtable source, oldest first:
        ``(i << 45) | key`` per source i keeps the concat globally sorted,
        so ONE searchsorted probes all runs and tiers at once (the per-run
        loop pays ~10 numpy dispatches per source).  Priority is the
        prefix: the highest hitting source is the newest.  Cached across
        gets; a single appended run extends the concat incrementally.
        Returns None (=> per-run fallback) for keys outside [0, 2^45)."""
        srcs = self._tiers[::-1] + self._runs
        ids = tuple(id(s[0]) for s in srcs)
        c = self._mbt
        if c is not None and c[0] == ids:
            return c[1], c[2], c[3]
        lim = np.int64(1) << self._MEM_SHIFT
        n_src = len(srcs)
        assert n_src < (1 << 18)         # source ids must fit 63-45 bits
        if c is not None and len(ids) == len(c[0]) + 1 \
                and c[0] == ids[:-1]:
            rk = srcs[-1][0]             # one new run appended at the end
            if len(rk) and (rk[0] < 0 or rk[-1] >= lim):
                self._mbt = None
                return None, None, None
            nprev = len(c[0])
            assert nprev < n_src         # its band is the next source id
            T = np.concatenate(
                [c[1], (np.int64(nprev) << self._MEM_SHIFT) + rk])
            offs = c[2] + [len(c[1])]
        else:
            parts = []
            for i, (rk, _w, _v) in enumerate(srcs):
                if len(rk) and (rk[0] < 0 or rk[-1] >= lim):
                    self._mbt = None
                    return None, None, None
                parts.append((np.int64(i) << self._MEM_SHIFT) + rk)
            T = np.concatenate(parts) if parts else np.empty(0, np.int64)
            offs, o = [], 0
            for p in parts:
                offs.append(o)
                o += len(p)
        self._mbt = (ids, T, offs, srcs)
        return T, offs, srcs

    # ----------------------------------------------------------------- cache
    def _sets(self, keys: np.ndarray) -> np.ndarray:
        h = keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        h ^= h >> np.uint64(29)
        return ((h >> np.uint64(1)).astype(np.int64) % self.cache_sets)

    def _cache_update(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Insert/overwrite entries (CLOCK eviction within each set)."""
        if len(keys) == 0:
            return
        # dedupe (last wins) to avoid write conflicts in the vectorized scatter
        self._cache_apply(*self._dedup_newest(keys, vals))

    def _cache_apply(self, keys: np.ndarray, vals: np.ndarray,
                     fresh: bool = False) -> None:
        """``_cache_update`` for already-deduped sorted (keys, vals).

        ``fresh=True`` asserts no key is currently cached (the level-read
        admission path: those keys just missed the probe in the same
        ``get_batch`` call), skipping the pointless hit scan."""
        if len(keys) == 0:
            return
        sets = self._sets(keys)
        if self._cache_virgin:           # first fill: no hits possible and
            self._cache_virgin = False   # every CLOCK scan lands instantly
            self._clock_fill_virgin(sets, keys, vals)
            return
        if fresh:
            ms, mk, mv = sets, keys, vals
        else:
            match = self.cache_keys[sets] == keys[:, None]
            way = match.argmax(axis=1)      # see get_batch: fused any+argmax
            hit = match[np.arange(len(keys)), way]
            hi = np.flatnonzero(hit)
            sh, wh = sets[hi], way[hi]
            self.cache_vals[sh, wh] = vals[hi]
            self.cache_ref[sh, wh] = 1
            # misses: CLOCK — evict first way with ref=0, clearing refs as
            # we pass.  Vectorized across sets: misses are grouped by set
            # (stable, so ascending-key insertion order is preserved) and
            # inserted in rounds — round r does every set's r-th pending
            # insert at once.  Bit-for-bit equivalent to the sequential
            # per-entry CLOCK scan.
            miss = np.flatnonzero(~hit)
            if not len(miss):
                return
            ms, mk, mv = sets[miss], keys[miss], vals[miss]
        # radix-sortable set indices (see _clock_fill_virgin)
        ss = ms.astype(np.uint16) if self.cache_sets <= (1 << 16) else ms
        order = np.argsort(ss, kind="stable")
        ms, mk, mv = ms[order], mk[order], mv[order]
        if len(ms) == 1 or (ms[1:] != ms[:-1]).all():
            self._clock_insert(ms, mk, mv)   # all sets distinct: one round
            return
        if self.cache_ways == 8:
            self._clock_insert_multi(ms, mk, mv)
            return
        rank = np.arange(len(ms)) - np.searchsorted(ms, ms, side="left")
        for r in range(int(rank.max()) + 1):
            sel = rank == r
            self._clock_insert(ms[sel], mk[sel], mv[sel])

    def _clock_fill_virgin(self, sets: np.ndarray, keys: np.ndarray,
                           vals: np.ndarray) -> None:
        """Closed-form CLOCK state after inserting into an all-empty cache.

        Starting from hand=0/ref=0, the r-th insert into a set provably goes
        to way ``r % W`` (a full pass clears every ref, so the wrapped scan
        again stops immediately), leaving hand = count % W and ref = 1
        exactly for the ways of the last incomplete pass (all ways when the
        count divides evenly).  Bit-identical to the sequential scan, with
        no per-round work.
        """
        # set indices come from ``_sets`` (mod cache_sets), but arrive here
        # as a bare parameter: pin the range the uint16 radix cast needs
        assert int(sets.min()) >= 0 and int(sets.max()) < self.cache_sets
        # numpy's stable argsort radix-sorts <=16-bit ints (13x faster than
        # the int64 mergesort); set indices usually fit
        ss = sets.astype(np.uint16) if self.cache_sets <= (1 << 16) else sets
        order = np.argsort(ss, kind="stable")     # key-ascending within set
        self._fill_virgin_sorted(sets[order], keys[order], vals[order])

    def _fill_virgin_sorted(self, s: np.ndarray, k: np.ndarray,
                            v: np.ndarray) -> None:
        """_clock_fill_virgin body for input already sorted by (set, key)."""
        W = self.cache_ways
        n = len(s)
        change = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
        cnt = np.diff(np.r_[change, n])
        rank = np.arange(n) - np.repeat(change, cnt)
        way = (rank % W).astype(np.int32)
        # winners per (set, way) are exactly the last min(count, W) entries
        # of each set group (their ways are distinct by construction)
        mask = (np.repeat(change + cnt, cnt) - np.arange(n)) <= W
        self.cache_keys[s[mask], way[mask]] = k[mask]
        self.cache_vals[s[mask], way[mask]] = v[mask]
        us = s[change]
        m = (cnt % W).astype(np.int32)
        self.cache_hand[us] = m
        self.cache_ref[us] = ((np.arange(W)[None, :] < m[:, None])
                              | (m[:, None] == 0)).astype(np.int8)

    def _clock_insert(self, s: np.ndarray, k: np.ndarray, v: np.ndarray
                      ) -> None:
        """One CLOCK insertion per (distinct) set in ``s``, vectorized.

        Per set: scan ways from the hand, clearing ref bits as we pass,
        until a way with ref=0 is found (if all refs were set, the full
        pass clears them and the original hand position is the victim).
        """
        W = self.cache_ways
        hand = self.cache_hand[s]
        if not self.cache_ref[s, hand].any():
            # every hand already points at a ref=0 victim: no scan, no
            # ref clearing — insert at the hand and advance it
            self.cache_keys[s, hand] = k
            self.cache_vals[s, hand] = v
            self.cache_ref[s, hand] = 1
            self.cache_hand[s] = (hand + 1) % W
            return
        if W == 8:
            # pack each set's ref row into a byte; the scan (find first
            # zero from the hand, clearing passed refs) becomes rotate +
            # two table lookups — no [m, W] index matrices
            bits = (self.cache_ref[s].astype(np.uint8) @ _CLOCK_POW2
                    ).astype(np.uint16)
            h = hand.astype(np.uint16)
            rot_bits = ((bits >> h) | (bits << (8 - h))) & np.uint16(0xFF)
            j = _CLOCK_FIRST_ZERO[rot_bits]
            has0 = j < 8
            j = np.where(has0, j, 0)
            slot = ((hand + j) % W).astype(np.int32)
            # cleared prefix in the rotated frame, rotated back
            pre = np.where(has0, (1 << j) - 1, 0xFF).astype(np.uint16)
            mask = ((pre << h) | (pre >> (8 - h))) & np.uint16(0xFF)
            new_bits = (bits & ~mask) | (1 << slot)
            self.cache_ref[s] = _CLOCK_UNPACK[new_bits & 0xFF]
            self.cache_keys[s, slot] = k
            self.cache_vals[s, slot] = v
            self.cache_hand[s] = (slot + 1) % W
            return
        rot = (hand[:, None] + np.arange(W, dtype=np.int32)) % W
        refs = self.cache_ref[s[:, None], rot]                  # [m, W]
        zero = refs == 0
        has0 = zero.any(axis=1)
        j = np.where(has0, zero.argmax(axis=1), 0)
        # clear refs the hand passed over (all W ways when none were zero)
        clear = np.arange(W)[None, :] < j[:, None]
        clear[~has0] = True
        rows = np.broadcast_to(s[:, None], rot.shape)
        self.cache_ref[rows[clear], rot[clear]] = 0
        slot = rot[np.arange(len(s)), j]
        self.cache_keys[s, slot] = k
        self.cache_vals[s, slot] = v
        self.cache_ref[s, slot] = 1
        self.cache_hand[s] = (slot + 1) % W

    def _clock_insert_multi(self, ms: np.ndarray, mk: np.ndarray,
                            mv: np.ndarray) -> None:
        """Sequential CLOCK insertions with repeated sets, W == 8 only.

        ``ms`` is sorted by set (stable, so per-set insertion order is the
        arrival order).  Equivalent to the round loop over ``_clock_insert``
        but the packed ref byte and hand live in local arrays across rounds
        — the cache arrays are read once and written once, instead of a
        gather/scatter per round.
        """
        W = self.cache_ways
        n = len(ms)
        change = np.flatnonzero(np.r_[True, ms[1:] != ms[:-1]])
        us = ms[change]
        cnt = np.diff(np.r_[change, n])
        bits = (self.cache_ref[us].astype(np.uint8) @ _CLOCK_POW2
                ).astype(np.uint16)
        hand = self.cache_hand[us].astype(np.uint16)
        slots = np.empty(n, np.int64)
        for r in range(int(cnt.max())):
            act = np.flatnonzero(cnt > r)
            b, h = bits[act], hand[act]
            rot = ((b >> h) | (b << (8 - h))) & np.uint16(0xFF)
            j = _CLOCK_FIRST_ZERO[rot]
            has0 = j < 8
            j = np.where(has0, j, 0)
            slot = (h + j) % W
            pre = np.where(has0, (1 << j) - 1, 0xFF).astype(np.uint16)
            mask = ((pre << h) | (pre >> (8 - h))) & np.uint16(0xFF)
            bits[act] = ((b & ~mask) | (1 << slot)) & np.uint16(0xFF)
            hand[act] = (slot + 1) % W
            slots[change[act] + r] = slot
        self.cache_ref[us] = _CLOCK_UNPACK[bits & 0xFF]
        self.cache_hand[us] = hand.astype(np.int32)
        lin = ms * W + slots
        if (cnt > W).any():
            # > W inserts into one set can revisit a slot; keep the last
            # write per (set, way) so the scatter below is conflict-free
            order = np.argsort(lin, kind="stable")
            ll = lin[order]
            keep = order[np.flatnonzero(np.r_[ll[1:] != ll[:-1], True])]
            lin, mk, mv = lin[keep], mk[keep], mv[keep]
        self.cache_keys.reshape(-1)[lin] = mk
        self.cache_vals.reshape(-1, self.cache_vals.shape[-1])[lin] = mv

    @property
    def cache_capacity(self) -> int:
        return self.cache_sets * self.cache_ways

    def prewarm_cache(self, keys: np.ndarray, vals: np.ndarray,
                      rng: np.random.Generator | None = None) -> None:
        """Fill the cache to capacity with a uniform sample of the live
        entries — steady-state emulation so short observation windows see
        the equilibrium hit rate rather than a cold-start transient."""
        if len(keys) == 0:
            return
        with span("lsm.prewarm_cache"):
            cap = self.cache_capacity
            if len(keys) > cap:
                rng = rng or np.random.default_rng(0)
                idx = rng.choice(len(keys), cap, replace=False)
                keys, vals = keys[idx], vals[idx]
            # A fresh cache takes the closed-form virgin fill, whose first step
            # re-sorts the (key-sorted) batch by set.  Fuse both sorts into ONE
            # argsort of (set << 47) | key — same final (set, key) order, one
            # mergesort cheaper per prewarm.  Duplicate keys collide in the
            # packed word exactly when they collide as keys (same key => same
            # set), so the dedup fallback check carries over.
            if (self._cache_virgin and len(keys) > 1
                    and self.cache_sets <= (1 << 15)
                    and int(keys.min()) >= 0 and int(keys.max()) < (1 << 47)):
                sets = self._sets(keys)
                comb = (sets << np.int64(47)) | keys
                order = np.argsort(comb, kind="stable")
                ck = comb[order]
                if not (ck[1:] == ck[:-1]).any():
                    self._cache_virgin = False
                    self._fill_virgin_sorted(sets[order], keys[order],
                                             vals[order])
                    self.metrics.reset()
                    return
            # store-derived keys are unique, so sorting alone reproduces
            # _cache_update's dedup ordering; fall back to the deduping path
            # if a caller hands us duplicates
            order = stable_argsort_keys(keys)
            sk = keys[order]
            if len(sk) > 1 and (sk[1:] == sk[:-1]).any():
                self._cache_update(keys, vals)
            else:
                self._cache_apply(sk, vals[order])
            self.metrics.reset()

    # ------------------------------------------------------------- snapshots
    def snapshot(self) -> dict:
        """Epoch-barrier snapshot (Flink-checkpoint analogue).  Carries the
        delta weights so a restore preserves the Z-set, not just the
        last-write-wins view."""
        with span("lsm.snapshot"):
            keys, weights, vals = self._items_weighted()
        return {"keys": keys, "vals": vals, "weights": weights,
                "memory_mb": self.memory_mb, "value_words": self.value_words}

    @classmethod
    def restore(cls, snap: dict, *, memory_mb: float | None = None,
                **kw) -> "LSMStore":
        store = cls(memory_mb if memory_mb is not None else snap["memory_mb"],
                    value_words=snap["value_words"], **kw)
        if len(snap["keys"]):
            w = snap.get("weights")
            store._push_run(np.asarray(snap["keys"], np.int64),
                            np.ones(len(snap["keys"]), np.int64) if w is None
                            else np.asarray(w, np.int64),
                            np.asarray(snap["vals"], np.int32))
        return store
