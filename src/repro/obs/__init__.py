"""Observability for the serving stack: per-request span trees, a bounded
flight recorder, and the cost-model calibration audit.

Stdlib only at import (not even numpy): `api.executor`, `api.ragdb`,
`serving.scheduler`, and `serving.faults` all thread trace context through
their hot paths, so this package must be importable from every layer
without creating a cycle, and the disabled fast path must cost one
attribute check. jax is imported lazily, on the enabled path only: the
first span an enabled tracer opens imports `jax.profiler`, so that spans
recorded under an active profiler session also land in the profiler's
trace as ``rag.*`` host events (see `repro.obs.tracer`).
"""
from repro.obs.calibration import CalibrationTable, pow2_bucket
from repro.obs.recorder import FlightRecorder
from repro.obs.tracer import (NULL_SPAN, NULL_TRACE, FanSpan, Span, Trace,
                              TraceGroup, Tracer)

__all__ = [
    "CalibrationTable", "pow2_bucket", "FlightRecorder", "Tracer", "Trace",
    "Span", "FanSpan", "TraceGroup", "NULL_TRACE", "NULL_SPAN",
]
