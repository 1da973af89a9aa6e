"""Ahead-of-time compiles of the kernel shapes a cell launches.

The store pads every kernel operand up a power-of-two ladder
(``repro.kernels.device.bucket``), so a cell launches a few dozen padded
shapes.  Its traffic file lists them, as ``bench/shapes.py`` recorded
them from the cell's own runs::

    {"sorted_probe": [[table, queries], ...],
     "window_agg": [[events, segments, value_rows], ...]}

Each is lowered and compiled through the kernel's own jitted entry with
the arguments the store passes, which puts it in that entry's cache (and
in the persistent cache) without running it: nothing compiles in the
window.  A kernel that is gone is skipped.
"""
from __future__ import annotations

import importlib


def _kernel(module: str, name: str):
    try:
        return getattr(importlib.import_module(module), name)
    except (ImportError, AttributeError):
        return None


def compile_shapes(shapes: dict) -> int:
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    n = 0
    fn = _kernel("repro.kernels.sorted_probe.kernel", "sorted_probe")
    if fn is not None:
        for t, q in shapes.get("sorted_probe", []):
            fn.lower(sds((t,), jnp.int32), sds((t,), jnp.int32),
                     sds((q,), jnp.int32), sds((q,), jnp.int32),
                     interpret=False).compile()
            n += 1
    fn = _kernel("repro.kernels.window_agg.kernel", "window_agg")
    if fn is not None:
        for e, s, rows in shapes.get("window_agg", []):
            fn.lower(sds((e,), jnp.int32), sds((rows, e), jnp.float32), s,
                     interpret=False).compile()
            n += 1
    return n
