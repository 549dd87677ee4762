"""repro.npec.obs — observability for the serving stack.

Four pieces (docs/observability.md):

* :class:`Tracer` / :data:`NULL_TRACER` (tracer.py): cycle-stamped
  span/instant events for request lifecycles and per-overlay unit
  activity, strictly opt-in with a no-op fast path;
* :class:`MetricsRegistry` (metrics.py): counters, labeled counter
  families and exact cycle histograms — the registry behind
  ``EngineStats`` / ``FleetStats`` / ``StreamCache`` reports;
* export/schema/profile: Chrome trace-event / Perfetto JSON export
  (``launch/serve.py --trace out.json``), the event-schema checker, and
  the ``python -m repro.npec.obs.profile`` cycle-sink CLI;
* spans.py: the served path's wall-clock span names and `OP_CLASS`,
  `jax.profiler.TraceAnnotation`s on the profiler's (and the chip's)
  clock — the one piece that measures host time, not modelled cycles.
"""

from repro.npec.obs.export import (dumps_trace, trace_to_dict,
                                   write_chrome_trace)
from repro.npec.obs.metrics import Counter, CycleHistogram, MetricsRegistry
from repro.npec.obs.schema import (ATTR_CATEGORY, METRIC_COUNTERS,
                                   METRIC_FAMILIES, METRIC_HISTOGRAMS,
                                   REQUEST_INSTANTS, REQUEST_SPANS,
                                   STREAM_KINDS, validate_trace)
from repro.npec.obs.spans import (ENGINE_ADMIT, ENGINE_DECODE, ENGINE_SYNC,
                                  EXEC_EXECUTE, EXEC_PREFIX,
                                  EXEC_PREPARE_WEIGHTS, EXEC_QUANTIZE_WEIGHT,
                                  EXEC_TRACE,
                                  OP_CLASS, SESSION_LOAD_SLOT,
                                  SESSION_MIGRATE, SESSION_RESET_SLOT,
                                  node_class, span)
from repro.npec.obs.tracer import NULL_TRACER, NullTracer, Tracer, UNITS

__all__ = [
    "ATTR_CATEGORY", "Counter", "CycleHistogram",
    "ENGINE_ADMIT", "ENGINE_DECODE", "ENGINE_SYNC", "EXEC_EXECUTE",
    "EXEC_PREFIX", "EXEC_PREPARE_WEIGHTS", "EXEC_QUANTIZE_WEIGHT",
    "EXEC_TRACE", "METRIC_COUNTERS",
    "METRIC_FAMILIES", "METRIC_HISTOGRAMS", "MetricsRegistry",
    "NULL_TRACER", "NullTracer", "OP_CLASS", "REQUEST_INSTANTS",
    "REQUEST_SPANS", "SESSION_LOAD_SLOT", "SESSION_MIGRATE",
    "SESSION_RESET_SLOT", "STREAM_KINDS", "Tracer", "UNITS", "dumps_trace",
    "node_class", "span", "trace_to_dict", "validate_trace",
    "write_chrome_trace",
]
