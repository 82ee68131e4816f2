"""Observability for the planning stack: spans, metrics, exporters.

Import discipline mirrors ``repro.analysis.registry``: this package is
stdlib-only so the hot core modules (``plan_broker``,
``planning_backend``, ``selinger``) can bind the singletons at import
time with zero added dependencies.  See README.md in this directory for
the span model and the overhead contract.
"""
from repro.obs.exporters import (attribution_md, wave_summary,
                                 write_attribution, write_chrome_trace)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               get_metrics)
from repro.obs.tracer import NULL_SPAN, Span, Tracer, get_tracer, \
    trace_enabled

__all__ = [
    "NULL_SPAN", "Span", "Tracer", "get_tracer", "trace_enabled",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_metrics",
    "attribution_md", "wave_summary", "write_attribution",
    "write_chrome_trace", "ALWAYS_ON",
]

# Counters the planner increments whether tracing is on or off, each at
# most once per scan call, stacked group or synchronous flush (README,
# "Overhead contract").  A counter is created at its first increment, so
# one of these missing from a snapshot has counted 0.
ALWAYS_ON = (
    "backend.compiles",            # XLA compiles and compile-cache loads
    "backend.launches",            # scan program launches
    "backend.scan_spans",          # spans those programs sweep
    "backend.programs_built",      # program-memo misses
    "backend.decode_table_dims",   # value-table dims decoded, per build
    "broker.sweep_rows.sync",      # grid rows swept by synchronous flushes
    "broker.sweep_rows.async",     # grid rows swept by flush_async waves
    "broker.sync_flushes.result",  # flush() forced by PlanFuture.result()
    "broker.sync_flushes.retry",   # ensemble -> grid scan_fallback retries
    "broker.sync_flushes.explicit",  # any other caller of flush()
)
