"""Profiler-clock spans and in-program counters: the wall-time half of
:mod:`repro.obs`.

``span(name, **meta)`` marks one call into a layer of the store, its
kernels or the engine as a ``jax.profiler.TraceAnnotation``.  The
profiler stamps each span on its own clock, the one on which it also
stamps host launches and device operations, so a profiler trace can put
the device's idle time down to the span open on the host.  This module
reads no clock: nothing here takes or returns a time, and a span records
nothing unless a profiler trace is running.

Spans are off by default.  Off, ``span`` is one global read that returns
one shared ``contextlib.nullcontext()``: it formats no string and
imports nothing, so the numpy store path never loads ``jax``.  What a
call site passes (the keyword arguments of ``span``, the step number of
``step``) is still evaluated.  ``enable(True)`` turns them on.

``counts`` holds integer counters that count whether spans are on or
off.  Each kernel's host wrapper adds, per device call,
``<kernel>.calls`` and ``<kernel>.h2d_bytes``, the padded host operands
it sends to the device.  The probe sends a table once:
``sorted_probe.table_uploads`` counts tables sent (their bytes go to
``h2d_bytes`` then) and ``sorted_probe.table_reuses`` the calls whose
table was already on the device.  ``window_agg.remapped`` counts the
aggregate calls whose ids had to be sorted on the host first (0 on the
store's path, which sends sorted ranks).  The hopping-window count adds
``hop.updates``, the unique (key, window) pairs it writes, and
``hop.fired``, the windows whose hot item it emitted.
"""
from __future__ import annotations

import collections
import contextlib

# every span the program opens
SPANS = (
    "engine.tick", "engine.process", "engine.emit", "engine.reconfigure",
    "engine.install", "engine.partition",
    "lsm.get_batch", "lsm.put_batch", "lsm.snapshot", "lsm.probe",
    "lsm.segment_sum", "lsm.read.memtable", "lsm.read.cache",
    "lsm.read.levels", "lsm.flush", "lsm.compact", "lsm.consolidate",
    "lsm.install_run", "lsm.prewarm_cache",
    "sorted_probe.prepare", "sorted_probe.launch", "sorted_probe.wait",
    "window_agg.prepare", "window_agg.launch", "window_agg.wait",
    "kernel.first_call",
    "hop.assign", "hop.combine", "hop.fire", "hop.expire",
)

counts: collections.Counter = collections.Counter()

_NULL = contextlib.nullcontext()
_on = False
_annotate = None            # jax.profiler.TraceAnnotation, once enabled
_step = None                # jax.profiler.StepTraceAnnotation
_shapes_seen: set = set()   # (kernel, padded shape) called in this process


def enable(on: bool) -> None:
    """Turn spans on or off for the whole process."""
    global _on, _annotate, _step
    if on and _annotate is None:
        import jax.profiler
        _annotate = jax.profiler.TraceAnnotation
        _step = jax.profiler.StepTraceAnnotation
    _on = bool(on)


def span(name: str, **meta):
    """Context manager marking one call as ``name``; ``meta`` becomes the
    span's arguments in the trace."""
    if not _on:
        return _NULL
    return _annotate(name, **meta)


def step(name: str, num: int):
    """``span`` for one step of a loop: the spans opened inside share the
    step number ``num`` in the trace."""
    if not _on:
        return _NULL
    return _step(name, step_num=num)


def first_call(kernel: str, shape: tuple):
    """A ``kernel.first_call`` span around the first call of ``kernel`` at
    padded ``shape`` in this process (where a compile would land), and
    the null context on every later one."""
    key = (kernel, shape)
    if key in _shapes_seen:
        return _NULL
    _shapes_seen.add(key)
    if not _on:
        return _NULL
    return _annotate("kernel.first_call", kernel=kernel,
                     shape="x".join(map(str, shape)))
