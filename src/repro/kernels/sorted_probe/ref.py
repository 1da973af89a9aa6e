"""Numpy oracle for the sorted-run probe (LSM SSTable lookup)."""
from __future__ import annotations

import numpy as np


def sorted_probe_ref(table: np.ndarray, queries: np.ndarray):
    """table: [T] sorted int keys; queries: [N] int keys.

    Returns (pos [N] int32, found [N] bool): pos = number of table entries
    strictly less than the query (== insertion point == index of the match
    when present).
    """
    pos = np.searchsorted(table, queries, side="left")
    found = table[np.minimum(pos, len(table) - 1)] == queries
    return pos.astype(np.int32), found
