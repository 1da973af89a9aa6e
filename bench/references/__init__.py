"""Plain references, one module per operator semantics, found by the
``semantics`` name of a configuration.  Each module gives
``initial_state(config, rng)``, ``partition_key(state_keys)`` and a
``Reference(config, initial, weights)`` with ``process``, ``state`` and
``must_keep``."""
from __future__ import annotations

import numpy as np


def last_occurrence(keys: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sorted unique keys, the index of each one's last occurrence, and
    its count."""
    rev = keys[::-1]
    uq, first, cnt = np.unique(rev, return_index=True, return_counts=True)
    return uq, len(keys) - 1 - first, cnt


def bf16_round(w: np.ndarray) -> np.ndarray:
    """Integer weights rounded to the nearest bfloat16 (ties to even)."""
    f = np.asarray(w, np.float32).view(np.uint32)
    f = (f + np.uint32(0x7FFF) + ((f >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return f.view(np.float32).astype(np.int64)
