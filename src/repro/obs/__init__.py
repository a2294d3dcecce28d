"""Unified serving telemetry: tracing, metrics, profiling hooks.

One observability plane over the search/exec/serve stacks — zero cost
when off, deterministic where it must be:

  * :mod:`repro.obs.trace` — span API (``span(...)`` context manager +
    ``event(...)`` instant marks) wired through the mixer, the serving
    drivers, the guarded runtime, and calibration; exports Chrome
    trace-event JSON and a deterministic ``stable_trace`` projection.
  * :mod:`repro.obs.metrics` — named counters/gauges/histograms with
    adapters over the five pre-existing measurement sources
    (``instrument()``, memo stats, kernel-cache stats, StragglerMonitor,
    HealthReport); JSON + Prometheus text exposition exports.
  * :mod:`repro.obs.profile` — opt-in ``jax.profiler`` capture, inside
    which the spans above are ``TraceAnnotation`` events and the device ops
    carry the program's name scopes.

Surfaced by the serve CLI's ``--trace PATH`` / ``--metrics PATH`` flags
and measured by ``bench_serve``'s ``serve_telemetry_overhead`` row.
"""

from repro.obs.metrics import (MetricsRegistry, collect_caches, collecting,
                               current_metrics, ingest_health,
                               ingest_instrument, ingest_kernel_cache,
                               ingest_memo_stats, ingest_straggler)
from repro.obs.profile import jax_trace
from repro.obs.trace import (Tracer, current_tracer, event, span, trace_id,
                             tracing)

__all__ = [
    "MetricsRegistry", "Tracer",
    "collect_caches", "collecting", "current_metrics", "current_tracer",
    "event", "ingest_health", "ingest_instrument", "ingest_kernel_cache",
    "ingest_memo_stats", "ingest_straggler", "jax_trace", "span",
    "trace_id", "tracing",
]
