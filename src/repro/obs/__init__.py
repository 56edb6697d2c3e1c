"""Unified telemetry: metrics registry, span tracer, logging, progress.

One stdlib-only subsystem behind every counter, latency histogram, trace
span, log line and SSE progress event in the DSE stack::

    from repro import obs

    REQS = obs.registry().counter("cim_http_requests_total", "...",
                                  ("endpoint", "method"))
    with obs.span("engine.compile", bucket=str(key)):
        ...
    obs.get_logger("server").debug("GET /v1/stats 200")
    obs.progress_bus().publish(job_key, phase="race", rung=1, best=2.4)

See ``docs/observability.md`` for the metric catalog and span names.
"""
from repro.obs import profile
from repro.obs.events import ProgressBus, progress_bus
from repro.obs.profile import (
    MeasurementRecord,
    record_measurements,
    run_microbench,
    take_measurements,
)
from repro.obs.log import configure_logging, get_logger
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    StatCounters,
    exemplars_enabled,
    registry,
)
from repro.obs.recorder import (
    TIMELINE_SCHEMA,
    FlightRecorder,
    flight_recorder,
    regret_curve,
    render_timeline,
)
from repro.obs.trace import (
    Span,
    Tracer,
    chrome_trace,
    current_span,
    record,
    span,
    tracer,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "StatCounters",
    "registry",
    "exemplars_enabled",
    "DEFAULT_BUCKETS",
    "Span",
    "Tracer",
    "tracer",
    "span",
    "record",
    "current_span",
    "chrome_trace",
    "FlightRecorder",
    "flight_recorder",
    "render_timeline",
    "regret_curve",
    "TIMELINE_SCHEMA",
    "profile",
    "MeasurementRecord",
    "run_microbench",
    "record_measurements",
    "take_measurements",
    "configure_logging",
    "get_logger",
    "ProgressBus",
    "progress_bus",
]
