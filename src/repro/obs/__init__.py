"""Unified telemetry: metric sketches, span tracing, timeline export.

Everything the store/transport/cluster/cache stack emits flows through
this package (DESIGN.md §13):

  * `repro.obs.metrics` — counters, gauges, mergeable log-scale
    histogram sketches (p50/p90/p99/p999 within ~2.2% of exact);
  * `repro.obs.trace`   — nested spans with injectable clocks, causal
    parent/child links, and point events (doorbells, retries, fence
    waits, resize cohort moves, cache validate/fill, failover phases);
  * `repro.obs.export`  — Chrome-trace/Perfetto JSON + flat metrics
    JSON, byte-identical for same-seed runs;
  * `repro.obs.report`  — ``python -m repro.obs.report <base>`` renders
    the per-phase latency/throughput table and the CI ``--check`` gate.

Instrumented code imports the free functions::

    from repro import obs
    with obs.span("cluster.write", node=n):
        obs.event("rdma.doorbell", verbs=3)
        obs.get_registry().counter("rdma.posts").inc()

Both no-op (or hit the process-default registry) unless a tracer /
registry is installed — `obs.scope()` swaps in a fresh pair for traced
drills and restores on exit.  `obs.device_span()` is the device path's
span: it goes to the JAX profiler while it records
(``jax.profiler.start_trace``), on the device trace's clock, and never
to a tracer.
"""

from repro.obs.export import (METRICS_SUFFIX, TRACE_SUFFIX,
                              chrome_trace_events, export_payloads,
                              export_strings, load_export, write_export)
from repro.obs.metrics import (GROWTH, Counter, Gauge, Histogram,
                               MetricsRegistry, percentiles_from)
from repro.obs.trace import (Span, TickClock, Tracer, device_span, event,
                             get_registry, get_tracer, install, scope,
                             set_registry, span)

__all__ = [
    "GROWTH", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "percentiles_from",
    "Span", "TickClock", "Tracer", "device_span", "event", "get_registry",
    "get_tracer", "install", "scope", "set_registry", "span",
    "METRICS_SUFFIX", "TRACE_SUFFIX", "chrome_trace_events",
    "export_payloads", "export_strings", "load_export", "write_export",
]
