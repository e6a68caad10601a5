"""repro.obs — deterministic tracing + streaming metrics for the control plane.

Three pieces (see ``docs/observability.md``):

  - :mod:`repro.obs.trace` — process-global span tracer (sim-time + wall
    time), Chrome ``trace_event`` export and a text flamegraph;
  - :mod:`repro.obs.metrics` — typed counters/gauges/histograms sampled
    periodically into JSONL;
  - :mod:`repro.obs.report` / ``python -m repro.obs report`` — the offline
    reader (per-stage latency breakdown, fairness-over-time table).

Layering rule: ``repro.service`` and ``repro.core`` import ``repro.obs``,
never the reverse — this package is stdlib+numpy only (no jax, no solver
imports) so it can wrap any tier without cycles. All instrumentation is a
no-op until a tracer/registry is installed (``set_tracer``/``set_metrics``);
the enabled cost is measured on the chip by the benchmark's traced runs
(``docs/observability.md``, Overhead).
"""
from . import clock
from .metrics import (Counter, Gauge, Histogram, JsonlSink, MetricsRegistry,
                      SAMPLE_SCHEMA, get_metrics, set_metrics)
from .trace import (CHROME_SCHEMA, NULL_SPAN, Tracer, get_tracer, instant,
                    set_tracer, span)
from .util import json_safe, tally

__all__ = [
    "clock",
    "CHROME_SCHEMA", "NULL_SPAN", "Tracer", "get_tracer", "set_tracer",
    "span", "instant",
    "SAMPLE_SCHEMA", "Counter", "Gauge", "Histogram", "JsonlSink",
    "MetricsRegistry", "get_metrics", "set_metrics",
    "json_safe", "tally",
]
