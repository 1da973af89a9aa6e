"""Numpy oracle for keyed window aggregation (segment sum + count)."""
from __future__ import annotations

import numpy as np


def window_agg_ref(seg_ids: np.ndarray, values: np.ndarray, n_segments: int):
    """seg_ids: [N] int in [0, n_segments); values: [N, V] float32.

    Returns (sums [n_segments, V], counts [n_segments]) as float32.
    """
    sums = np.zeros((n_segments, values.shape[1]), np.float64)
    np.add.at(sums, seg_ids, values)
    counts = np.bincount(seg_ids, minlength=n_segments)
    return sums.astype(np.float32), counts.astype(np.float32)
