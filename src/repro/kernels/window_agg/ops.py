"""Host-side wrapper for keyed window aggregation.

A device call opens three spans (``repro.obs.spans``):
``window_agg.prepare`` (padding and the transpose to lane-major rows on
the host), ``window_agg.launch`` (the jitted call, which stages the host
operands and launches the kernel) and ``window_agg.wait`` (copying the
sums back, which waits for the device), and counts the call and the
bytes it sends.

The kernel needs ids in ascending order, which the store's calls always
give (the ranks of key-sorted deltas).  Other ids are first sorted on the
host, with their values, by one stable argsort; the sums come out per id
as before.  ``window_agg.remapped`` counts the calls that needed it.
"""
from __future__ import annotations

import numpy as np

from repro.kernels.device import bucket
from repro.kernels.window_agg.ref import window_agg_ref
from repro.obs.spans import counts, first_call, span


def aggregate(seg_ids: np.ndarray, values: np.ndarray, n_segments: int, *,
              impl: str) -> tuple[np.ndarray, np.ndarray]:
    """seg_ids in [0, n_segments); values [N, V].  Returns (sums [S, V],
    counts [S]) as float32 host arrays.

    impl: "pallas" (compiled TPU kernel) | "interpret" (the same kernel in
    the Pallas interpreter) | "ref" (numpy oracle).  The kernel sums the
    values, with events and segments padded up the ``bucket`` ladder
    (padded events carry segment id -1, which matches nothing); counts are
    a host bincount.  Ids not in ascending order are sorted first
    (counter ``window_agg.remapped``)."""
    seg_ids = np.asarray(seg_ids, np.int32)
    values = np.asarray(values, np.float32)
    n, v = values.shape
    if n == 0 or n_segments == 0:
        return np.zeros((n_segments, v), np.float32), \
            np.zeros(n_segments, np.float32)
    if impl == "ref":
        return window_agg_ref(seg_ids, values, n_segments)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown aggregate impl {impl!r}")
    from repro.kernels.window_agg.kernel import (EVENT_TILE, SEG_BLOCK,
                                                 window_agg)
    nb, sb = bucket(n, EVENT_TILE), bucket(n_segments, SEG_BLOCK)
    with span("window_agg.prepare"):
        if np.any(seg_ids[1:] < seg_ids[:-1]):
            order = np.argsort(seg_ids, kind="stable")
            seg_ids, values = seg_ids[order], values[order]
            counts["window_agg.remapped"] += 1
        seg = np.full(nb, -1, np.int32)
        seg[:n] = seg_ids
        rows = np.zeros((v, nb), np.float32)
        rows[:, :n] = values.T
    with span("window_agg.launch"), first_call("window_agg", (nb, sb, v)):
        sums = window_agg(seg, rows, sb, interpret=impl == "interpret")
    with span("window_agg.wait"):
        sums = np.asarray(sums)
    counts["window_agg.calls"] += 1
    counts["window_agg.h2d_bytes"] += 4 * nb * (1 + v)  # int32 ids, f32 rows
    per_seg = np.bincount(seg_ids, minlength=n_segments)
    return sums[:, :n_segments].T, per_seg.astype(np.float32)
