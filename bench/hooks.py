"""Host timers and profiler annotations around calls into each layer.

Installed for the traced run only, by patching the named attribute of a
class of the system for the run's lifetime.  A site whose class or
attribute is gone is skipped, and the metrics that read it come out
null.  Sites:

=====================  =============================================
annotation             call
=====================  =============================================
``bench.tick``         ``StreamEngine.run_tick``
``engine.reconfigure`` ``StreamEngine.reconfigure``
``engine.install``     ``StreamEngine._install_partitions``
``lsm.get_batch``      ``LSMStore.get_batch``
``lsm.put_batch``      ``LSMStore.put_batch``
``lsm.snapshot``       ``LSMStore.snapshot``
``lsm.probe``          ``LSMStore._probe_run`` (the probe's call site)
``lsm.segment_sum``    ``LSMStore._segment_sum`` (the weight sum's)
``source.generate``    the benchmark's event generator
=====================  =============================================

The two call sites also keep each call's unpadded shape for the
roofline: ``(table, queries)`` and ``(events, segments, value_rows)``.
"""
from __future__ import annotations

import functools
import importlib
import time
from collections import defaultdict

SITES = (
    ("bench.tick", "repro.streaming.engine", "StreamEngine", "run_tick"),
    ("engine.reconfigure", "repro.streaming.engine", "StreamEngine",
     "reconfigure"),
    ("engine.install", "repro.streaming.engine", "StreamEngine",
     "_install_partitions"),
    ("lsm.get_batch", "repro.state.lsm", "LSMStore", "get_batch"),
    ("lsm.put_batch", "repro.state.lsm", "LSMStore", "put_batch"),
    ("lsm.snapshot", "repro.state.lsm", "LSMStore", "snapshot"),
    ("lsm.probe", "repro.state.lsm", "LSMStore", "_probe_run"),
    ("lsm.segment_sum", "repro.state.lsm", "LSMStore", "_segment_sum"),
)
SOURCE = "source.generate"

# unpadded operand shapes of a call, from its arguments after ``self``
SHAPES = {
    "lsm.probe": lambda a: (len(a[0]), len(a[1])),
    "lsm.segment_sum": lambda a: (len(a[0]), len(a[1]), 1),
}


class Hooks:
    def __init__(self):
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self.seconds: dict[str, float] = defaultdict(float)
        self.shapes: dict[str, list] = defaultdict(list)
        self.installed: list[str] = []
        self._undo: list[tuple] = []

    def _wrap(self, name: str, fn, shape=None):
        annotate, seconds = self._annotate, self.seconds
        shapes = self.shapes[name]

        @functools.wraps(fn)
        def timed(*args, **kw):
            t0 = time.perf_counter()
            with annotate(name):
                out = fn(*args, **kw)
            seconds[name] += time.perf_counter() - t0
            if shape is not None:
                shapes.append(shape(args[1:]))
            return out
        return timed

    def install(self) -> None:
        for name, module, cls_name, attr in SITES:
            try:
                cls = getattr(importlib.import_module(module), cls_name)
            except (ImportError, AttributeError):
                continue
            fn = cls.__dict__.get(attr)
            if fn is None:
                continue
            self._undo.append((cls, attr, fn))
            setattr(cls, attr, self._wrap(name, fn, SHAPES.get(name)))
            self.installed.append(name)

    def wrap_source(self, gen):
        """The generator wrapped as the ``source.generate`` site."""
        self.installed.append(SOURCE)
        return self._wrap(SOURCE, gen)

    def uninstall(self) -> None:
        for cls, attr, fn in reversed(self._undo):
            setattr(cls, attr, fn)
        self._undo.clear()

    def share(self, name: str, of: float):
        """Host seconds in ``name`` as a percentage of ``of`` seconds; None
        where the site was not installed."""
        if name not in self.installed or of <= 0:
            return None
        return 100.0 * self.seconds[name] / of
