"""Unified runtime telemetry: spans, metrics, exporters, drift.

The observability layer the ISSUE-10 tentpole asks for.  Four pieces:

* :mod:`repro.obs.spans` — monotonic-clock span recorder on a
  preallocated ring buffer (nesting + thread id, no allocation on the
  hot path).  Off by default; ``obs.enable()`` turns recording on, and
  mirrors each span into the JAX profiler's trace.
* :mod:`repro.obs.metrics` — named counters / gauges / histograms on a
  process-global registry, always on, with pull-style collectors for
  surfaces that keep their own counters (the plan-cache ledger,
  ``ServeEngine.plan_report()``).
* :mod:`repro.obs.export` — the merged Perfetto timeline (live spans on
  pid 1 next to ``sim.to_chrome_trace``'s modeled/measured tracks on
  pid 0), Prometheus text exposition, JSON snapshot.
* :mod:`repro.obs.drift` — online modeled-vs-measured drift: executed
  segments become ``calib.Measurement`` rows with a rolling geomean
  ratio per (segment, target), flagged when it leaves the PR-9 band.

Importing this package never pulls jax — instrumented planner modules
stay importable in jax-free tooling.
"""
from . import drift, export, metrics, spans
from .drift import DEFAULT_BAND, DriftMonitor
from .export import (merged_chrome_trace, metrics_snapshot,
                     prometheus_text, write_merged_trace,
                     write_prometheus)
from .metrics import (REGISTRY, Counter, Gauge, Histogram,
                      MetricsRegistry, collect, counter, gauge,
                      histogram, register_collector)
from .spans import (Span, SpanRecorder, begin, disable, enable, enabled,
                    end, record, recorder, span, switch)

__all__ = [
    "spans", "metrics", "export", "drift",
    # spans
    "Span", "SpanRecorder", "enable", "disable", "enabled", "recorder",
    "begin", "end", "switch", "record", "span",
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "counter", "gauge", "histogram", "register_collector", "collect",
    # export
    "merged_chrome_trace", "write_merged_trace", "prometheus_text",
    "write_prometheus", "metrics_snapshot",
    # drift
    "DriftMonitor", "DEFAULT_BAND",
]
