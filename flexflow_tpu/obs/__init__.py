"""flexflow_tpu.obs — the one observability plane (docs/observability.md).

Three legs, one package:

* :mod:`~flexflow_tpu.obs.trace` — request-scoped span tracing: every
  ``submit()`` (dense, generation, fleet) and every ``fit()`` dispatch
  window gets monotonic-ns spans on the injectable clock, sampled via
  ``FFConfig.trace_sample_rate`` and exportable as Chrome-trace JSON
  (``flexflow-tpu trace export``);
* :mod:`~flexflow_tpu.obs.flight` — the flight recorder: a bounded ring
  of recent fflogger events + spans, dumped to ``FF_FLIGHT_DIR`` on
  health-state edges, dispatch errors, supervisor attempt failures and
  fatal exceptions (``flexflow-tpu flight dump/show``);
* :mod:`~flexflow_tpu.obs.registry` — typed counters/gauges/histograms
  with a Prometheus text-exposition renderer and an optional stdlib
  HTTP scrape endpoint (``--metrics-port``).  ServingMetrics /
  GenerationMetrics / the train loop FEED the registry: the
  ``serve_stats`` / ``gen_stats`` events are views over it, so the
  event stream and the scrape endpoint cannot diverge.

:mod:`~flexflow_tpu.obs.events` is the event-name registry every
``fflogger.Category.event`` call site must draw from (repo_lint RL011
pins it statically — a typo'd event name used to vanish silently from
harvesters like ``calibrate``'s ``capture_events`` hook).
"""

from .events import EVENTS, declared_events
from .flight import FlightRecorder, flight_dump, get_flight
from .registry import (MetricsRegistry, get_registry, start_metrics_server,
                       validate_prometheus_text)
from .trace import (TERMINAL_PHASES, Tracer, get_tracer, phase_of,
                    to_chrome, tracer_from_config, validate_chrome_trace,
                    validate_raw_trace)

__all__ = [
    "EVENTS", "declared_events",
    "FlightRecorder", "get_flight", "flight_dump",
    "MetricsRegistry", "get_registry", "start_metrics_server",
    "validate_prometheus_text",
    "TERMINAL_PHASES", "Tracer", "get_tracer", "phase_of", "to_chrome",
    "tracer_from_config", "validate_chrome_trace", "validate_raw_trace",
]
