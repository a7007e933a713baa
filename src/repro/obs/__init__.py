"""Observability for the Sphere repro: span tracing + metrics registry.

- :mod:`repro.obs.trace` — nested-span tracer with explicit clock injection
  (the same virtual-clock discipline as ``TenantQueue`` /
  ``ReplicationDaemon``), Chrome/Perfetto ``trace_event`` export and a
  plain-text flame summary; every span also annotates a ``jax.profiler``
  session.
- :mod:`repro.obs.layers` — the layer scopes of the fused SPMD program and
  the op->layer map of the program that ran last.
- :mod:`repro.obs.metrics` — process-wide registry of counters, gauges and
  fixed-bucket histograms behind one ``snapshot()`` / ``to_json()`` API.

Both executors accept a tracer (``Dataflow.run(executor, data, trace=...)``)
and publish into the default registry; see docs/OBSERVABILITY.md.
"""

from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.trace import NULL_TRACER, Span, TraceBuffer, Tracer

__all__ = ["Tracer", "TraceBuffer", "Span", "NULL_TRACER",
           "MetricsRegistry", "REGISTRY"]
