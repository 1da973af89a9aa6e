"""Least bytes any implementation of the store's two device operations
must move, and the chip's peaks to turn them into least time.

The bytes count the real (unpadded) operands of each call, so that no
kernel, whatever it pads or tiles, can read over 100%:

* probe of N queries into a sorted table of T keys: the queries
  (8 bytes each), at least min(N, T) table keys (8 bytes each), and the
  results (a 4-byte position and a found byte per query);
* segment sum of N events with V value rows into S segments: segment
  ids (4 bytes an event), values (4 bytes a row an event), sums (4 bytes
  a row a segment).

The compute bound is left out: the v5e publishes no peak for 32-bit
vector compares, and its bf16/int8 matrix peaks do not bound them.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def probe_bytes(table: int, queries: int) -> int:
    return 8 * queries + 8 * min(queries, table) + 5 * queries


def segment_sum_bytes(events: int, segments: int, rows: int) -> int:
    return 4 * events + 4 * rows * events + 4 * rows * segments


def least_seconds(total_bytes: float, device_kind: str) -> float:
    return total_bytes / peaks(device_kind)["hbm_bytes_per_s"]
