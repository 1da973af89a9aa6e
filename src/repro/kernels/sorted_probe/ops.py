"""Host-side wrapper for the sorted-run probe."""
from __future__ import annotations

import numpy as np

from repro.kernels.device import bucket, dispatches
from repro.kernels.sorted_probe.ref import sorted_probe_ref

INT64_MAX = np.iinfo(np.int64).max


def split_keys(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 keys -> two int32 words whose signed lexicographic order is
    the int64 order, lossless over all of int64: ``hi = key >> 32`` and
    ``lo`` = the low half with its top bit flipped."""
    k = np.asarray(keys, np.int64)
    hi = (k >> 32).astype(np.int32)
    lo = ((k & 0xFFFFFFFF) ^ 0x80000000).astype(np.uint32).view(np.int32)
    return hi, lo


def probe(table: np.ndarray, queries: np.ndarray, *, impl: str
          ) -> tuple[np.ndarray, np.ndarray]:
    """Rank ``queries`` in the ascending int ``table`` (host arrays).

    impl: "pallas" (compiled TPU kernel) | "interpret" (the same kernel in
    the Pallas interpreter) | "ref" (numpy oracle).  Returns (pos [N] int32,
    found [N] bool); pos is the insertion point (== index of the match where
    found).  The kernel sees both operands padded up the ``bucket`` ladder
    with the maximum key; ``pos < len(table)`` masks those entries."""
    table = np.asarray(table, np.int64)
    queries = np.asarray(queries, np.int64)
    t, n = len(table), len(queries)
    if t == 0 or n == 0:
        return np.zeros(n, np.int32), np.zeros(n, bool)
    if impl == "ref":
        return sorted_probe_ref(table, queries)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown probe impl {impl!r}")
    from repro.kernels.sorted_probe.kernel import (QUERY_BLOCK, TABLE_TILE,
                                                   sorted_probe)
    tb = np.full(bucket(t, TABLE_TILE), INT64_MAX)
    tb[:t] = table
    qb = np.full(bucket(n, QUERY_BLOCK), INT64_MAX)
    qb[:n] = queries
    dispatches["sorted_probe"] += 1
    pos, found = sorted_probe(*split_keys(tb), *split_keys(qb),
                              interpret=impl == "interpret")
    pos = np.asarray(pos)[:n]
    return pos, np.asarray(found)[:n] & (pos < t)
