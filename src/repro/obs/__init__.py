"""Unified observability layer: metrics registry, trace spans, sidecar.

Public surface (the whole repo imports only from here)::

    from repro import obs

    obs.enable(trace_out="trace.jsonl")       # off by default
    obs.REGISTRY.counter("repro_queries_total").inc(tier="sieve")
    with obs.span("service.epoch", epoch=i) as sp: ...
    side = obs.Sidecar(board=svc.heartbeats, port=0)

Design contract (docs/observability.md has the full catalog):

  * Device-fed diagnostics are UNCONDITIONAL extra outputs of the existing
    compiled fns -- the traced program is identical with obs on or off, so
    instrumentation can never change ``retrace_count`` /
    ``query_trace_count`` / ``query_batch_trace_count``.  Enablement gates
    only the host side: device->host reads of those diagnostics and JSONL
    span emission.  Spans always open a profiler annotation (inactive, and
    sub-microsecond, when no profiler session is running).
  * Registry updates are always on (nanoseconds of locked dict math), so
    bench ``--json`` collections carry counter context even in the
    "disabled" configuration the regression gate times.
"""
from repro.obs.export import prometheus_text, stats_line, write_stats_json
from repro.obs.metrics import Counter, Gauge, Histogram, Registry, REGISTRY
from repro.obs.sidecar import Sidecar
from repro.obs.trace import (Span, disable, enable, enabled, span,
                             trace_out_path)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "Sidecar", "Span", "span",
    "enable", "disable", "enabled", "trace_out_path",
    "prometheus_text", "stats_line", "write_stats_json",
]
