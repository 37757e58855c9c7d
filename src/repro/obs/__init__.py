"""Unified round telemetry: metrics registry, span tracer, engine probes,
jit profiling hooks and the round-report renderer (DESIGN.md §15).

Quickstart::

    from repro.obs import RecordingProbe

    with RecordingProbe("run.jsonl", profiler=True) as probe:
        hist = run_federated(clients, test, flcfg, probe=probe)
    # then: python -m benchmarks.obs_report run.jsonl
"""

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      METRIC_KINDS, metric_kind)
from .trace import (SCHEMA_VERSION, Tracer, chrome_trace, load_trace,
                    validate_records, validate_trace, write_chrome_trace)
from .probe import (NULL_PROBE, NullProbe, RecordingProbe, RoundProbe,
                    as_probe)
from .jaxprof import JaxProfiler, JitEntry
from .scopes import SCOPES, scope
from .report import render_markdown, render_report, round_rows

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "METRIC_KINDS",
    "metric_kind",
    "SCHEMA_VERSION", "Tracer", "chrome_trace", "load_trace",
    "validate_records", "validate_trace", "write_chrome_trace",
    "NULL_PROBE", "NullProbe", "RecordingProbe", "RoundProbe", "as_probe",
    "JaxProfiler", "JitEntry", "SCOPES", "scope",
    "render_markdown", "render_report", "round_rows",
]
