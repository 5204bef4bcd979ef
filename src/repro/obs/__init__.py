"""Observability: span tracing, telemetry registry, exposition.

Three parts (ROADMAP: the telemetry layer every serving follow-on reports
through):

* ``obs.trace``    — the one span API: nested spans + point events into a
  bounded ring (JSONL export), each span also a ``jax.profiler`` annotation
  when profiling; the process :data:`~repro.obs.trace.TRACER` has both
  sinks off by default, ``ServerConfig(trace=True)`` switches the ring on
  and ``REPRO_PROFILE=1`` both.
* ``obs.registry`` — named counters/gauges/histograms (+ the opaque-key
  ``KeyedCounter`` backing ``core.plan.fused_trace_counts``) on the process
  :data:`~repro.obs.registry.REGISTRY`.
* ``obs.export`` renders the registry as Prometheus text and parses it
  back.

Import-order contract: ``repro.core.plan`` (pulled in by
``repro.core.__init__``) imports ``obs.registry``/``obs.trace`` at module
import time, so this package's imports must stay stdlib-only.
"""

from repro.obs import export, registry, trace  # noqa: F401

__all__ = ["export", "registry", "trace"]
