"""Observability: deterministic tracing, decision provenance, metrics.

Four pieces:

* :mod:`repro.obs.trace` — sim-time span tracer; ``NULL_TRACER`` is the
  O(1) disabled default every control-loop hook falls back to.
* :mod:`repro.obs.spans` — its wall-time half: profiler-clock spans
  (off by default) inside the store, its kernels and the engine, and
  the in-program ``counts`` (kernel calls, bytes moved, compares).
* :mod:`repro.obs.provenance` — ``Explain`` records (why a policy
  proposed what it proposed) and the ``HistoryRow.reason`` enum.
* :mod:`repro.obs.registry` — unified counters/gauges/histograms/timers
  behind one ``snapshot()``.

Exporters (JSONL + Chrome ``trace_event`` for Perfetto) live in
:mod:`repro.obs.export`.  Determinism contract: docs/observability.md.
"""
from repro.obs.export import (chrome_trace, read_jsonl, write_chrome,
                              write_jsonl)
from repro.obs.registry import NULL_REGISTRY, MetricsRegistry
from repro.obs.spans import SPANS, counts, enable, span
from repro.obs.trace import CATS, NULL_TRACER, Span, Tracer

# provenance imports the policy layer (``repro.core``); loaded on first
# use, so that the store and the kernels, which import ``spans``, do not
# load it
_PROVENANCE = ("REASONS", "Explain", "explain_admission", "reason_counts")


def __getattr__(name: str):
    if name in _PROVENANCE:
        from repro.obs import provenance
        return getattr(provenance, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "CATS", "Explain", "MetricsRegistry", "NULL_REGISTRY", "NULL_TRACER",
    "REASONS", "SPANS", "Span", "Tracer", "chrome_trace", "counts",
    "enable", "explain_admission", "read_jsonl", "reason_counts", "span",
    "write_chrome", "write_jsonl",
]
