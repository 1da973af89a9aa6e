"""Host-side wrapper for the sorted-run probe.

A device call opens three spans (``repro.obs.spans``):
``sorted_probe.prepare`` (padding and key split on the host),
``sorted_probe.launch`` (the jitted call, which stages the host operands
and launches the kernel) and ``sorted_probe.wait`` (copying the results
back, which waits for the device), and counts the call and the bytes
it sends.
"""
from __future__ import annotations

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
    tp, qp = bucket(t, TABLE_TILE), bucket(n, QUERY_BLOCK)
    with span("sorted_probe.prepare"):
        tb = np.full(tp, INT64_MAX)
        tb[:t] = table
        qb = np.full(qp, INT64_MAX)
        qb[:n] = queries
        words = (*split_keys(tb), *split_keys(qb))
    with span("sorted_probe.launch"), first_call("sorted_probe", (tp, qp)):
        pos, found = sorted_probe(*words, interpret=impl == "interpret")
    with span("sorted_probe.wait"):
        pos = np.asarray(pos)[:n]
        found = np.asarray(found)[:n]
    counts["sorted_probe.calls"] += 1
    counts["sorted_probe.h2d_bytes"] += 8 * (tp + qp)   # two int32 words a key
    return pos, found & (pos < t)
