"""Unified telemetry: span timelines, metrics registry, drift monitors.

Pure-stdlib package (no jax imports) threaded through comm / program /
planner / trainer / serving.  See ``docs/TELEMETRY.md`` for the metric
catalogue and usage recipes.

* :mod:`repro.telemetry.spans` -- nested span timelines with Chrome-trace
  (Perfetto) and plain-text exports; ingests live CommEvents; the same
  spans on a profiler's clock once a profiler sink is installed.
* :mod:`repro.telemetry.metrics` -- counters / gauges / fixed-bucket
  histograms with JSON-lines and Prometheus text exports; default-off
  module helpers plus per-component registries.
* :mod:`repro.telemetry.drift` -- rolling meas_over_est residuals per
  (flow, stage, domain) with structured profile-staleness warnings.
"""
from repro.telemetry.drift import (DEFAULT_BAND, DriftMonitor,
                                   ProfileStalenessWarning)
from repro.telemetry.metrics import (DECLARED, REGISTRY, MetricsRegistry,
                                     active_registry, inc, observe,
                                     scoped_metrics, set_gauge)
from repro.telemetry.metrics import disable as disable_metrics
from repro.telemetry.metrics import enable as enable_metrics
from repro.telemetry.metrics import enabled as metrics_enabled
from repro.telemetry.spans import (Tracer, current_tracer, maybe_instant,
                                   maybe_span)

__all__ = [
    "DECLARED", "DEFAULT_BAND", "DriftMonitor", "MetricsRegistry",
    "ProfileStalenessWarning", "REGISTRY", "Tracer", "active_registry",
    "current_tracer", "disable_metrics", "enable_metrics", "inc",
    "maybe_instant", "maybe_span", "metrics_enabled", "observe",
    "scoped_metrics", "set_gauge",
]
