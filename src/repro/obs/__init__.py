"""Service observability: schema-validated metrics with zero dependencies.

``obs/schema.py`` is the single table every metric name, kind, label set
and histogram bucket layout is defined in (and ``docs/METRICS.md`` is
generated from); ``obs/registry.py`` is the runtime — counters, gauges,
log-bucketed histograms on a process-wide ``MetricsRegistry``, a JSONL
sink flushed at segment boundaries, and an optional in-process HTTP
``/metrics`` + ``/statusz`` endpoint.  ``obs/trace.py`` adds the causal
layer — ring-buffered spans on a process-wide ``Tracer`` with JSONL and
Chrome/Perfetto exports — and ``obs/recorder.py`` the flight recorder
(per-island last-K boundary ring, post-mortem dumps on failure).
Instrumentation is host-side only: emitters pass scalars that already
crossed the device boundary at an existing segment-boundary pull, never
jax arrays (tests/test_obs.py pins both the device-sync count and the
segment-compile count against it).
"""
from repro.obs.registry import (Counter, Gauge, Histogram,     # noqa: F401
                                MetricsRegistry, metrics, read_jsonl,
                                reset_metrics, set_metrics,
                                start_metrics_server)
from repro.obs.schema import (SCHEMA, SPECS, MetricSpec,       # noqa: F401
                              log_buckets, render_markdown)
from repro.obs.trace import (Span, Tracer, reset_tracer,       # noqa: F401
                             set_annotator, set_tracer, to_chrome,
                             tracer, validate_chrome)
from repro.obs.recorder import (FlightRecorder, recorder,      # noqa: F401
                                reset_recorder, set_recorder)
