"""Host-side wrapper for keyed window aggregation."""
from __future__ import annotations

import numpy as np

from repro.kernels.device import bucket, dispatches
from repro.kernels.window_agg.ref import window_agg_ref


def aggregate(seg_ids: np.ndarray, values: np.ndarray, n_segments: int, *,
              impl: str) -> tuple[np.ndarray, np.ndarray]:
    """seg_ids in [0, n_segments); values [N, V].  Returns (sums [S, V],
    counts [S]) as float32 host arrays.

    impl: "pallas" (compiled TPU kernel) | "interpret" (the same kernel in
    the Pallas interpreter) | "ref" (numpy oracle).  The kernel sums the
    values, with events and segments padded up the ``bucket`` ladder
    (padded events carry segment id -1, which matches nothing); counts are
    a host bincount."""
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
    nb = bucket(n, EVENT_TILE)
    seg = np.full(nb, -1, np.int32)
    seg[:n] = seg_ids
    rows = np.zeros((v, nb), np.float32)
    rows[:, :n] = values.T
    dispatches["window_agg"] += 1
    sums = np.asarray(window_agg(seg, rows, bucket(n_segments, SEG_BLOCK),
                                 interpret=impl == "interpret"))
    counts = np.bincount(seg_ids, minlength=n_segments)
    return sums[:, :n_segments].T, counts.astype(np.float32)
