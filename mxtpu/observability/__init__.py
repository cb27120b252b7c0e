"""mxtpu.observability — unified observability: deterministic request
tracing, failure flight recorder, and one metrics registry across
serving and training (docs/observability.md).

Three modules, one discipline — what is asserted rides a counter clock,
so every trace, postmortem, and metrics delta is bit-reproducible under
the same seeds + fault plan and assertable in tier-1 (the one wall
clock, ``t_ns``, rides beside the tick and stays out of the
deterministic bytes):

- :mod:`~mxtpu.observability.trace` — process-wide :class:`Tracer`
  (off by default; ``MXTPU_TRACE=1`` or :func:`tracing`): typed
  spans/events with tick timestamps and correlation ids threaded along
  the existing rid <-> tag maps, covering the full request path
  (gateway admit/QoS wait -> router dispatch -> transport -> engine
  admission/prefix-hit/COW/swap/deferral -> prefill chunks, decode
  steps, draft/verify windows -> terminal state) plus guardian events
  and automatic events from every fired ``resilience.faults`` site;
  Chrome trace-event export (:func:`export_chrome_trace`) serves the
  tick traces and the legacy ``mxtpu.profiler`` events through one
  writer, and spans wrap in ``jax.profiler.TraceAnnotation`` when a
  profiler session runs.  Boundary spans (one per trainer step, engine
  iteration or phase) and XLA compilations are kept in a bounded ring
  whether the tracer is on or not (``Tracer.boundary_spans()``).
- :mod:`~mxtpu.observability.flight` — :class:`FlightRecorder`
  (``MXTPU_FLIGHT_BUFFER=N`` or :func:`flight_recording`): bounded
  per-request event rings that, on any failure path — quarantine,
  shed, replica death drain, guardian rollback, checkpoint corruption
  — snapshot the implicated requests' timelines plus a counters delta
  into deterministic, JSON-dumpable postmortems.
- :mod:`~mxtpu.observability.metrics` — one :class:`MetricsRegistry`
  with named lazy sources (engine/gateway/router/supervisor stats,
  resilience counters, guardian counters, CompileLedger per-site
  program counts, bulk-cache stats) flattened into a single snapshot
  with ``snapshot()``/``delta()`` and Prometheus-text + JSON
  exposition; ``tools/diagnose.py`` and the benchmark's runners
  collect through it.

Coverage is checked statically: the ``obs_check`` analysis pass (O001,
``python -m mxtpu.analysis obs``) asserts every declared fault site
resolves to a registered trace event type and every CompileLedger site
to a metrics key — observability is lost loudly, mirroring R005.
"""

from __future__ import annotations

from .flight import (FlightRecorder, Postmortem, flight_recording,
                     get_flight)
from .metrics import MetricsRegistry, default_registry, get_registry
from .trace import (BOUNDARY_TYPES, EVENT_TYPES, Span, TraceEvent, Tracer,
                    export_chrome_trace, gateway_rid, get_tracer, tracing)

__all__ = [
    "Tracer", "TraceEvent", "get_tracer", "tracing", "gateway_rid",
    "EVENT_TYPES", "BOUNDARY_TYPES", "Span", "export_chrome_trace",
    "FlightRecorder", "Postmortem", "get_flight", "flight_recording",
    "MetricsRegistry", "get_registry", "default_registry",
]
