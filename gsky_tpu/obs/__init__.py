"""Observability: request-scoped tracing, a flight recorder, and a
dependency-free Prometheus registry.

Three concerns, one seam:

* ``trace`` — a ContextVar-carried ``trace_id``/``span_id`` created at
  the OWS request boundary and threaded through the gateway, the tile
  stages, the export pipeline, and — via gRPC metadata —
  into the worker processes, whose child spans ride back on the RPC
  result and stitch into one tree.
* ``recorder`` — an always-on in-memory ring of the last N complete
  traces plus a reservoir of the slowest/degraded ones, dumped as JSONL
  on demand (``/debug/trace``) or automatically on SLO violation.
* ``prom`` — counters, gauges, and log-bucketed histograms rendered in
  Prometheus text exposition format at ``/metrics``.  Histograms are
  observed at the same measurement points that feed ``/debug`` so the
  two endpoints cannot drift; the rest is collected at scrape time from
  the live stats objects.

While a ``jax.profiler`` session runs, every span is also an event of
its name on the profile's host plane, on the device trace's clock.

``GSKY_TRACE=0`` disables tracing entirely (spans become no-ops on a
pre-checked fast path); ``GSKY_TRACE_FILE`` + ``GSKY_TRACE_SAMPLE``
enable sampled JSONL file export.  See docs/OBSERVABILITY.md.
"""

from .trace import (  # noqa: F401
    Span,
    Trace,
    adopt_spans,
    bind,
    current_context,
    current_span_id,
    current_trace,
    current_trace_id,
    event,
    record_span,
    remote_trace,
    set_attr,
    span,
    start_trace,
    trace_enabled,
    traceparent,
)
from .recorder import (  # noqa: F401
    FlightRecorder,
    default_recorder,
    reset_recorder,
)
from .prom import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
    log_buckets,
    parse_exposition,
    reset_registry,
)
from . import metrics  # noqa: F401  (registers default metric families)
from .metrics import (  # noqa: F401
    ENCODE_SECONDS,
    REQUESTS,
    REQUEST_SECONDS,
    RPC_SECONDS,
    STAGE_SECONDS,
    TRACE_EVENTS,
    render_metrics,
)
