"""Unified telemetry plane: tracing, metrics, and timeline export.

Three pieces, importable without JAX:

- :mod:`repro.obs.trace`   — span/event recorder (``Tracer``) with a strict
  no-op fast path (``NULL``) when tracing is disabled.
- :mod:`repro.obs.metrics` — counter/gauge/histogram registry plus the
  per-rule communication ledger (``CommLedger``) with JSONL and
  Prometheus-textfile sinks.
- :mod:`repro.obs.export`  — Chrome-trace/Perfetto JSON export and a
  dependency-free schema validator.

See ``src/repro/obs/README.md`` for the span taxonomy, sink formats, and
the overhead contract (disabled <2%, enabled <10% steps/sec): tracing off
is measured by every chip benchmark run (``bench/``, untraced), tracing on
by the traced run against it, recorded in ``PERF.md`` §7.
"""

from .trace import NULL, NullTracer, Tracer, as_tracer
from .metrics import CommLedger, MetricsRegistry, write_jsonl
from .export import to_chrome_trace, validate_chrome_trace, write_chrome_trace

__all__ = [
    "NULL",
    "NullTracer",
    "Tracer",
    "as_tracer",
    "CommLedger",
    "MetricsRegistry",
    "write_jsonl",
    "to_chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
]
