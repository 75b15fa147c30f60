"""Unified observability layer: span tracing + metrics.

Dependency-free (stdlib-only) measurement substrate for the whole repo:

  * ``trace``    — nested-span tracer (context-manager API, monotonic
    clocks, thread-safe, per-span attributes, true no-op when disabled)
    with Chrome ``trace_event`` JSON export loadable in Perfetto /
    ``chrome://tracing``, plus always-timed layer spans at the layer
    boundaries, kept in a bounded ring and mirrored into the
    ``jax.profiler`` trace;
  * ``metrics``  — process-wide registry of counters, gauges, and
    reservoir histograms (p50/p95/p99), exportable as JSON and the
    Prometheus text format;
  * ``validate`` — Chrome-trace schema/nesting/coverage validator
    (``python -m repro.obs.validate``), the CI gate for exported traces;
  * ``flight``   — convergence flight recorder: a bounded ring of
    per-round records (frontier, messages, estimate-drop histogram,
    device/host wall) captured in every execution mode, with opt-in
    per-vertex trajectory watchlists;
  * ``health``   — online invariant monitor over the flight stream
    (monotone estimates, frontier progress, message-bill
    mode-invariance) feeding anomalies into the tracer and a health
    gauge into the metrics registry;
  * ``http``     — dependency-free threaded endpoint serving
    ``/metrics``, ``/healthz``, and ``/debug/flight`` live
    (``kcore_serve --listen``).

The hot paths are instrumented permanently (host round loop, fused
runtime, streaming batch phases, window advances, the serving loop, XLA
compile durations via repro.core.jit_telemetry); detail spans cost
nothing until ``trace.enable()``, layer spans a few microseconds each —
surfaced as ``--trace out.json`` / ``--metrics`` on
``repro.launch.kcore_run`` and ``kcore_serve``.
"""

from repro.obs import flight, health, http, metrics, trace
from repro.obs.flight import FlightRecord, FlightRecorder, get_recorder
from repro.obs.health import InvariantMonitor, get_monitor
from repro.obs.http import ObsHTTPServer, start_server
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               get_registry)
from repro.obs.trace import LayerSpan, Span, Tracer, get_tracer
from repro.obs.validate import (TraceValidationError, span_tree_coverage,
                                validate_chrome_trace)

__all__ = [
    "trace",
    "metrics",
    "flight",
    "health",
    "http",
    "Tracer",
    "Span",
    "LayerSpan",
    "get_tracer",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "get_registry",
    "FlightRecorder",
    "FlightRecord",
    "get_recorder",
    "InvariantMonitor",
    "get_monitor",
    "ObsHTTPServer",
    "start_server",
    "validate_chrome_trace",
    "span_tree_coverage",
    "TraceValidationError",
]
