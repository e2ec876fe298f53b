"""Observability plane: solve traces, serving metrics, exporters.

Three sub-systems, one package (see ISSUE 7 / README "Observability"):

* :mod:`repro.obs.trace` — opt-in per-round solve traces
  (``EngineConfig(trace=True)``): an on-device ring of per-round records
  materialized host-side as :class:`SolveTrace`;
* :mod:`repro.obs.metrics` — the thread-safe :class:`MetricsRegistry`
  (counters / gauges / latency histograms) backing every serving-plane
  ``stats()``;
* :mod:`repro.obs.export` — Prometheus text exposition and JSONL
  snapshot dumps;
* :mod:`repro.obs.profiling` — ``jax.profiler`` trace annotations
  around engine builds, relax dispatch and the scheduler's host work,
  and the named phases of the jitted solve (``sssp.round``, ...,
  ``transition.pull``) with the table that joins a capture's device ops
  to them.

This package deliberately imports nothing from ``repro.core`` or
``repro.serve`` so every layer can depend on it without cycles.
"""
from .trace import (TRACE_COLUMNS, TRACE_COUNTER_COLUMNS, SolveTrace,
                    TraceBuf, materialize_trace, trace_append, trace_init)
from .metrics import (DEFAULT_LATENCY_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .export import parse_prometheus, to_prometheus, write_jsonl_snapshot
from .profiling import (PHASES, PROFILER_AVAILABLE, PhaseTable, annotate,
                        phase, phase_table)

__all__ = [
    "TRACE_COLUMNS", "TRACE_COUNTER_COLUMNS", "SolveTrace", "TraceBuf",
    "materialize_trace", "trace_append", "trace_init",
    "DEFAULT_LATENCY_BUCKETS", "Counter", "Gauge", "Histogram",
    "MetricsRegistry",
    "parse_prometheus", "to_prometheus", "write_jsonl_snapshot",
    "PHASES", "PROFILER_AVAILABLE", "PhaseTable", "annotate", "phase",
    "phase_table",
]
