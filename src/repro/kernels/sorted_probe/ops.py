"""Host-side wrapper for the sorted-run probe.

The store's runs never change once built, so a table's padded key words
go to the device once (``upload``, or ``ResidentTables`` at a run's
first probe) and every later ``probe`` of it sends only the queries.

A device call opens three spans (``repro.obs.spans``):
``sorted_probe.prepare`` (padding and key split on the host, of the
table too where it is uploaded for this call), ``sorted_probe.launch``
(the jitted call, which stages the host query words and launches the
kernel) and ``sorted_probe.wait`` (copying the results back, which
waits for the device).  It counts the call and the bytes it sends; an
upload counts itself and a call on a table uploaded before it counts a
reuse.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass

import numpy as np

from repro.kernels.device import bucket
from repro.kernels.sorted_probe.ref import sorted_probe_ref
from repro.obs.spans import counts, first_call, span

INT64_MAX = np.iinfo(np.int64).max


def split_keys(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 keys -> two int32 words whose signed lexicographic order is
    the int64 order, lossless over all of int64: ``hi = key >> 32`` and
    ``lo`` = the low half with its top bit flipped."""
    k = np.asarray(keys, np.int64)
    hi = (k >> 32).astype(np.int32)
    lo = ((k & 0xFFFFFFFF) ^ 0x80000000).astype(np.uint32).view(np.int32)
    return hi, lo


def _padded_words(keys: np.ndarray, size: int):
    """``keys`` padded to ``size`` with the maximum key, as split words."""
    padded = np.full(size, INT64_MAX)
    padded[:len(keys)] = keys
    return split_keys(padded)


@dataclass(eq=False)
class DeviceTable:
    """An ascending table's padded ``(hi, lo)`` key words on the device."""
    n: int              # entries before padding
    hi: object          # [bucket(n, TABLE_TILE)] int32 device arrays
    lo: object
    probes: int = 0     # calls that have probed it


def upload(table: np.ndarray) -> DeviceTable:
    """Pad ``table`` up the ``bucket`` ladder, split it into two int32
    words and put them on the default device, uncommitted (as a host
    operand of the jitted call would be, so the same compiled programs
    serve)."""
    import jax
    from repro.kernels.sorted_probe.kernel import TABLE_TILE
    table = np.asarray(table, np.int64)
    tp = bucket(len(table), TABLE_TILE)
    with span("sorted_probe.prepare"):
        hi, lo = jax.device_put(_padded_words(table, tp))   # one dispatch
    counts["sorted_probe.table_uploads"] += 1
    counts["sorted_probe.h2d_bytes"] += 8 * tp     # two int32 words a key
    return DeviceTable(len(table), hi, lo)


def probe(table, queries: np.ndarray, *, impl: str
          ) -> tuple[np.ndarray, np.ndarray]:
    """Rank ``queries`` in an ascending int ``table``: a host array, or
    (for the kernel) a ``DeviceTable``.

    impl: "pallas" (compiled TPU kernel) | "interpret" (the same kernel in
    the Pallas interpreter) | "ref" (numpy oracle, host tables only).
    Returns (pos [N] int32, found [N] bool); pos is the insertion point
    (== index of the match where found).  The kernel sees both operands
    padded up the ``bucket`` ladder with the maximum key; ``pos <
    len(table)`` masks those entries.  A host table is uploaded for this
    call alone."""
    queries = np.asarray(queries, np.int64)
    resident = isinstance(table, DeviceTable)
    if not resident:
        table = np.asarray(table, np.int64)
    t, n = (table.n if resident else len(table)), len(queries)
    if t == 0 or n == 0:
        return np.zeros(n, np.int32), np.zeros(n, bool)
    if impl == "ref":
        return sorted_probe_ref(table, queries)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown probe impl {impl!r}")
    from repro.kernels.sorted_probe.kernel import (QUERY_BLOCK, TABLE_TILE,
                                                   sorted_probe)
    if not resident:
        table = upload(table)
    elif table.probes:
        counts["sorted_probe.table_reuses"] += 1
    table.probes += 1
    tp, qp = bucket(t, TABLE_TILE), bucket(n, QUERY_BLOCK)
    with span("sorted_probe.prepare"):
        q_hi, q_lo = _padded_words(queries, qp)
    with span("sorted_probe.launch"), first_call("sorted_probe", (tp, qp)):
        pos, found = sorted_probe(table.hi, table.lo, q_hi, q_lo,
                                  interpret=impl == "interpret")
    with span("sorted_probe.wait"):
        pos = np.asarray(pos)[:n]
        found = np.asarray(found)[:n]
    counts["sorted_probe.calls"] += 1
    counts["sorted_probe.h2d_bytes"] += 8 * qp
    return pos, found & (pos < t)


class ResidentTables:
    """The ``DeviceTable`` of each host table probed, uploaded at its
    first probe and kept while the table lives.

    Keyed by the array's identity and guarded by a weak reference to it,
    so a reused ``id`` never hits; an entry goes when its array is freed,
    or when ``get`` uploads and ``live`` no longer holds it.  An uploaded
    array is made read-only: writing into it raises, where it would
    otherwise leave stale keys on the device."""

    def __init__(self):
        self._by_id: dict[int, tuple[weakref.ref, DeviceTable]] = {}

    def __len__(self) -> int:
        return len(self._by_id)

    def __contains__(self, table) -> bool:
        entry = self._by_id.get(id(table))
        return entry is not None and entry[0]() is table

    def get(self, table: np.ndarray, live) -> DeviceTable:
        """``table``'s device words, uploaded if they are not resident;
        an upload first drops every table that is not in ``live``."""
        key = id(table)
        if table in self:
            return self._by_id[key][1]
        keep = {id(a) for a in live}
        for k in [k for k in self._by_id if k not in keep]:
            del self._by_id[k]
        dev = upload(table)
        table.flags.writeable = False
        owner = weakref.ref(self)

        def freed(ref):
            tables = owner()
            if tables is not None and tables._by_id.get(key, (ref,))[0] is ref:
                tables._by_id.pop(key, None)

        self._by_id[key] = (weakref.ref(table, freed), dev)
        return dev
